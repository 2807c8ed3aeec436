"""Replicated cluster route table: topic filter → set of nodes.

Reference analog: the mria-replicated `emqx_route` bag table plus the
replicated trie (emqx_router.erl:75-84,111-125). Every node holds the FULL
cluster filter set (that is what lets publish route locally without a
network hop); the subscriber tables stay node-local.

Consistency split (mria parity, emqx_router.erl:111-125):
- plain-topic routes: dirty async replication (`emqx_router_utils`
  insert_direct_route) — eventual, per-filter ordered;
- wildcard routes: "transactional" — the writer waits for every reachable
  peer to ack before returning, because a half-replicated trie edge breaks
  matching (maybe_trans, emqx_router.erl:118-121).

The replica is matched on the host (a trie walk per topic of a batch, on
the sending node); the device match and the bitmaps of *local* subscribers
are applied on each owner node, where the forwarded batch rides the
broker's own route step.
"""

from __future__ import annotations

import threading
import zlib
from typing import Dict, List, Optional, Sequence, Tuple, Union

from emqx_tpu.broker.trie import TopicTrie
from emqx_tpu.ops import topics as T


class ShardOwnership:
    """Cluster-wide mesh-slice ownership (scale-out serving,
    docs/scale_out.md).

    Each serving node runs its own ('dp','tp') device mesh and owns a
    SLICE of the global subscriber-lane space — shard ids are
    ``s{index}/{total}`` plus the node's local mesh shape, advertised
    over the ``shard`` BPAPI proto on join (the mria-replicated
    ownership-table analog). The map answers two questions on the
    publish path:

    - which node currently serves a shard (``owner``), and
    - where publishes bound for a DEAD owner should go instead
      (``successor_node``): on node_down the dead node's home shards
      re-own onto survivors by rendezvous hash — every replica computes
      the same assignment with zero coordination RPCs — so the forward
      path reroutes to the adopting slice instead of stalling behind the
      dead peer's send deadline (the degrade ladder's cluster breakers
      already fail those sends fast; this gives them a live target).
      A returning owner re-advertises and reclaims its home shards.
    """

    def __init__(self, node: str, metrics=None) -> None:
        self.node = node
        self.metrics = metrics
        self._lock = threading.Lock()
        # shard id -> current owner node          guarded-by: _lock
        self._owner: Dict[str, str] = {}
        # node -> (home shard ids, mesh shape)    guarded-by: _lock
        self._home: Dict[str, Tuple[List[str], Tuple[int, int]]] = {}
        self._local: List[str] = []  # guarded-by: _lock

    @staticmethod
    def slice_ids(index: int, total: int) -> List[str]:
        """Shard ids of cluster slice `index` of `total` (one global
        slice per node today; the id scheme leaves room for splitting a
        slice finer than a node later)."""
        if not (0 <= index < total):
            raise ValueError(f"shard slice {index}/{total} out of range")
        return [f"s{index}/{total}"]

    # -- advertisement (BPAPI `shard` proto) -------------------------------
    def advertise_local(
        self, mesh_shape: Tuple[int, int], index: int, total: int
    ) -> List[str]:
        shards = self.slice_ids(index, total)
        self.advertise(self.node, shards, tuple(mesh_shape))
        with self._lock:
            self._local = list(shards)
        return shards

    def advertise(
        self, node: str, shards: List[str],
        mesh_shape: Tuple[int, int] = (0, 0),
    ) -> None:
        """A node announcing its home slice (join or node_up return):
        home shards return to their advertiser — reclaim is part of the
        rebalance ladder, not a special case."""
        with self._lock:
            self._home[node] = (list(shards), tuple(mesh_shape))
            for s in shards:
                self._owner[s] = node

    def local_shards(self) -> List[str]:
        with self._lock:
            return list(self._local)

    def label(self) -> str:
        """Span/metric label for this node's slice ("local" when no
        slice is advertised — a standalone mesh broker)."""
        with self._lock:
            if not self._local:
                return "local"
            shape = self._home.get(self.node, ((), (0, 0)))[1]
            lbl = "+".join(self._local)
            if shape != (0, 0):
                lbl += f"@dp{shape[0]}tp{shape[1]}"
            return lbl

    # -- reads -------------------------------------------------------------
    def owner(self, shard: str) -> Optional[str]:
        with self._lock:
            return self._owner.get(shard)

    def shard_count(self) -> int:
        with self._lock:
            return len(self._owner)

    def successor_node(self, dead: str) -> Optional[str]:
        """The node serving `dead`'s FIRST home shard now (None while
        the map has no better answer than the dead node itself)."""
        with self._lock:
            home = self._home.get(dead, ((), None))[0]
            for s in home:
                cur = self._owner.get(s)
                if cur is not None and cur != dead:
                    return cur
        return None

    # -- rebalance ladder --------------------------------------------------
    def reown(self, dead: str, survivors: List[str]) -> List[Tuple[str, str]]:
        """Reassign every shard `dead` owned onto `survivors` by
        rendezvous hash (deterministic: all replicas agree without a
        coordination round). Returns [(shard, new_owner)] moves; counts
        each into `mesh.shard.rebalance`."""
        cands = sorted(n for n in survivors if n != dead)
        moves: List[Tuple[str, str]] = []
        with self._lock:
            for s, cur in list(self._owner.items()):
                if cur != dead:
                    continue
                if not cands:
                    del self._owner[s]  # no survivor: orphan, not a lie
                    continue
                new = max(
                    cands,
                    key=lambda n: zlib.crc32(f"{s}|{n}".encode()),
                )
                self._owner[s] = new
                moves.append((s, new))
        if self.metrics is not None:
            for _ in moves:
                self.metrics.inc("mesh.shard.rebalance")
        return moves

    # -- bootstrap ---------------------------------------------------------
    def dump(self) -> List[tuple]:
        with self._lock:
            return [
                (n, list(shards), list(shape))
                for n, (shards, shape) in self._home.items()
            ]

    def load(self, dump: List[tuple]) -> None:
        for n, shards, shape in dump:
            self.advertise(n, list(shards), tuple(shape))


class ClusterRouteTable:
    """One node's replica of the global route table.

    Host-side only: exact filters are keys of `_dests` itself, wildcard
    filters also enter a plain `TopicTrie`. (The replica never matches on
    a device — the broker's own router does, on the receiving node — so it
    keeps no `RouteIndex`: building one cost 85 % of every replicated
    add.) A filter's owners are stored as one node name, or a tuple of
    names where several nodes subscribe it: strings and tuples of strings
    are no work for the cyclic GC, a million sets are."""

    def __init__(self, node: str) -> None:
        self.node = node
        self._trie = TopicTrie()
        # filter -> owner node name | tuple of owner names
        self._dests: Dict[str, Union[str, Tuple[str, ...]]] = {}  # guarded-by: _lock
        self._routes = 0  # (filter, node) pairs held  guarded-by: _lock
        self._lock = threading.Lock()

    # -- replica writes (applied locally AND via RPC from peers) ----------
    def _add(self, filter_: str, node: str) -> None:  # holds-lock: _lock
        cur = self._dests.get(filter_)
        if cur is None:
            self._dests[filter_] = node
            if T.wildcard(filter_):
                self._trie.insert(filter_)
        elif cur == node or (type(cur) is tuple and node in cur):
            return
        else:
            self._dests[filter_] = (
                cur + (node,) if type(cur) is tuple else (cur, node)
            )
        self._routes += 1

    def _delete(self, filter_: str, node: str) -> None:  # holds-lock: _lock
        cur = self._dests.get(filter_)
        if cur is None:
            return
        if type(cur) is tuple:
            if node not in cur:
                return
            rest = tuple(n for n in cur if n != node)
            self._dests[filter_] = rest[0] if len(rest) == 1 else rest
        elif cur == node:
            del self._dests[filter_]
            if T.wildcard(filter_):
                self._trie.delete(filter_)
        else:
            return
        self._routes -= 1

    def add_route(self, filter_: str, node: str) -> None:
        with self._lock:
            self._add(filter_, node)

    def delete_route(self, filter_: str, node: str) -> None:
        with self._lock:
            self._delete(filter_, node)

    def apply_batch(self, ops: Sequence[Tuple[str, str]], node: str) -> None:
        """Ordered `(op, filter)` pairs of one origin node (`op` is "add"
        or "delete"), under one take of the lock: the `route` v2 wire
        form, one slice of a replicated batch."""
        add, delete = self._add, self._delete
        with self._lock:
            for op, filter_ in ops:
                if op == "add":
                    add(filter_, node)
                else:
                    delete(filter_, node)

    def cleanup_node(self, node: str) -> int:
        """Purge all routes owned by a dead node (emqx_router_helper:135-148).

        The reference serializes this under a global lock so only one
        surviving node runs the mnesia transaction; here every node purges
        its own replica, which is the equivalent end state.
        """
        with self._lock:
            before = self._routes
            for filter_, cur in list(self._dests.items()):
                if cur == node or (type(cur) is tuple and node in cur):
                    self._delete(filter_, node)
            return before - self._routes

    # -- bootstrap (mria replica catch-up on join) -------------------------
    @staticmethod
    def _nodes(cur) -> Tuple[str, ...]:
        return cur if type(cur) is tuple else (cur,)

    def dump(self) -> List[tuple]:
        with self._lock:
            return [
                (f, sorted(self._nodes(ns))) for f, ns in self._dests.items()
            ]

    def load(self, dump: List[tuple]) -> None:
        with self._lock:
            for filter_, nodes in dump:
                for n in nodes:
                    self._add(filter_, n)

    def local_filters(self) -> List[str]:
        """The filters this node itself owns (what a joiner pushes)."""
        me = self.node
        with self._lock:
            return [
                f for f, cur in self._dests.items()
                if cur == me or (type(cur) is tuple and me in cur)
            ]

    # -- reads -------------------------------------------------------------
    def _match(self, topic: str) -> Dict[str, List[str]]:  # holds-lock: _lock
        out: Dict[str, List[str]] = {}
        dests = self._dests
        filters = self._trie.match(topic)
        if topic in dests and not T.wildcard(topic):
            filters.append(topic)
        for f in filters:
            cur = dests.get(f)
            if cur is None:
                continue
            for n in cur if type(cur) is tuple else (cur,):
                got = out.get(n)
                if got is None:
                    out[n] = [f]
                else:
                    got.append(f)
        return out

    def match_dests(self, topic: str) -> Dict[str, List[str]]:
        """topic -> {node: [matched filters]} (emqx_router:match_routes)."""
        with self._lock:
            return self._match(topic)

    def match_dests_batch(
        self, topics: List[str]
    ) -> List[Dict[str, List[str]]]:
        """Batch form: every topic under one take of the lock."""
        with self._lock:
            return [self._match(t) for t in topics]

    def has_route(self, filter_: str) -> bool:
        with self._lock:
            return filter_ in self._dests

    def routes(self) -> List[tuple]:
        with self._lock:
            return [
                (f, n)
                for f, ns in self._dests.items()
                for n in sorted(self._nodes(ns))
            ]

    def stats(self) -> Dict[str, int]:
        """Running counts: the harness polls this while a million routes
        replicate onto the loop that answers it."""
        with self._lock:
            return {
                "routes.count": self._routes,
                "topics.count": len(self._dests),
            }
