"""ClusterNode: one broker node wired into the cluster fabric.

Composes the local pub/sub kernel (`Broker`) with:
- membership (ekka parity) with route GC on nodedown
  (emqx_router_helper.erl:96,135-148),
- the replicated route table (mria parity),
- BPAPI-versioned RPC protos: broker-forward, route replication, channel
  registry, cluster config log — mirroring the reference's four proto
  families (apps/emqx/src/proto/: broker, cm, persistent_session, emqx),
- cross-node publish forwarding with per-node aggre dedup
  (emqx_broker.erl:262-293): ONE forward per (message, node) carrying the
  matched filters so the owner node skips re-matching,
- cluster-wide clientid→node channel registry (emqx_cm_registry parity),
- replicated config transaction log (emqx_cluster_rpc parity).

`make_cluster(n)` builds an n-node in-process cluster on a LocalBus — the
analog of the reference's slave-node CT harness
(emqx_router_helper_SUITE.erl:61, emqx_cluster_rpc_SUITE.erl:25-27).
"""

from __future__ import annotations

import asyncio
import collections
import concurrent.futures
import logging
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.message import Message
from emqx_tpu.broker.shared_sub import stable_hash
from emqx_tpu.cluster.cluster_rpc import ClusterRpcLog
from emqx_tpu.cluster.membership import Membership
from emqx_tpu.cluster.route_sync import ClusterRouteTable, ShardOwnership
from emqx_tpu.cluster.rpc import Rpc, RpcError
from emqx_tpu.cluster.tcp_transport import CH_FORWARD, CH_ROUTE
from emqx_tpu.cluster.transport import LocalBus
from emqx_tpu.mqtt import packet as pkt
from emqx_tpu.observe import profiler as _prof
from emqx_tpu.ops import topics as T

log = logging.getLogger("emqx_tpu.cluster")

ROUTE_BATCH_MAX = 4096  # (op, filter) pairs one `route` v2 apply_batch carries
ROUTE_SLICE_S = 0.005  # what one applied slice should take of the loop
ROUTE_SLICE_MIN, ROUTE_SLICE_MAX = 64, 4096
FWD_GROUP_MAX = 8192  # messages one forward call carries (queued batches joined)
LANE_WORKERS = 32  # lanes draining at once (two per peer: forwards, routes)


class _Lane:
    """One ordered queue to one peer (forwards, or route ops), drained by
    at most one worker at a time: a slow peer delays only its own lane."""

    __slots__ = ("items", "lock", "running", "seq")

    def __init__(self) -> None:
        self.items: collections.deque = collections.deque()
        self.lock = threading.Lock()
        self.running = False
        self.seq = 0  # forwards: the last sequence number shipped


class _Confirm:
    """The forwards one dispatched batch handed off (`rpc_mode: sync`):
    `future` resolves once every destination node confirmed its own, or
    gave it up with the node down."""

    __slots__ = ("future", "_left", "_lock")

    def __init__(self, n: int) -> None:
        self.future: concurrent.futures.Future = concurrent.futures.Future()
        self._left = n
        self._lock = threading.Lock()

    def one_done(self) -> None:
        with self._lock:
            self._left -= 1
            done = self._left == 0
        if done:
            self.future.set_result(None)


class ClusterNode:
    def __init__(
        self,
        name: str,
        bus: LocalBus,
        clock: Optional[Callable[[], float]] = None,
        broker: Optional[Broker] = None,
        forward_mode: str = "async",
        loop=None,
    ) -> None:
        """`loop`: when this node wraps a LIVE BrokerApp broker, incoming
        rpc handlers must run on the app's event loop — a forward's
        dispatch writes to client sockets, which asyncio transports only
        allow from their own thread. The bus thread then blocks on the
        loop's result (calls need replies); casts drain the same way."""
        self.name = name
        self.bus = bus
        self._loop = loop
        # app mode: replication rpcs must not block the event loop on a
        # peer round-trip (and an in-process peer pair would deadlock) —
        # a SINGLE worker preserves add/delete ordering per node
        self._repl_pool = (
            ThreadPoolExecutor(max_workers=1, thread_name_prefix=f"repl-{name}")
            if loop is not None
            else None
        )
        # the lanes' workers (forwards and route batches, one ordered
        # lane per peer and kind, `_lane_put`): a slow receiver (a cold
        # jit compile holds a confirmed reply up to ~40s, a GC pass ~3s)
        # delays its own lane and nothing else. Without a loop (library
        # mode) a lane drains on the caller's thread.
        self._fwd_pool = (
            ThreadPoolExecutor(
                max_workers=LANE_WORKERS, thread_name_prefix=f"lane-{name}"
            )
            if loop is not None
            else None
        )
        self._lanes: Dict[Tuple[str, str], _Lane] = {}  # guarded-by: _lanes_lock
        self._lanes_lock = threading.Lock()
        self._leaving = False  # guarded-by: _lanes_lock
        # what a forward is known by at its destination: this node's name
        # and this incarnation (a restarted node counts from 1 again)
        self._epoch = f"{os.getpid()}.{time.time_ns()}"
        # origin node -> {"epoch", "done": highest sequence applied,
        # "result": the last group's, "running": (first, future) | None}
        self._fwd_in: Dict[str, dict] = {}  # guarded-by: _fwd_in_lock
        self._fwd_in_lock = threading.Lock()
        self._confirms: List[_Confirm] = []  # not yet taken by a settle
        self._unconfirmed = 0  # guarded-by: _lanes_lock
        self._route_slice = 256  # ops per applied slice, steered to ROUTE_SLICE_S
        self.broker = broker or Broker()
        self.routes = ClusterRouteTable(name)
        # mesh-slice ownership (scale-out serving): which node serves
        # which slice of the global subscriber-lane space, and where
        # publishes bound for a dead owner reroute (docs/scale_out.md)
        self.shards = ShardOwnership(name, metrics=self.broker.metrics)
        self.membership = Membership(name, bus, clock=clock)
        self.rpc = Rpc(name, bus)
        self.conf_log = ClusterRpcLog(name)
        self.forward_mode = forward_mode
        self._chan_lock = threading.Lock()
        # clientid -> (node, sid): replicated channel registry
        self._channels: Dict[str, Tuple[str, str]] = {}
        # persistent-session router state (emqx_session_router parity):
        # locally parked sessions + the replicated clientid -> owner map
        self._parked: Dict[str, Dict] = {}
        self._parked_owner: Dict[str, str] = {}
        # guards park["pending"] swaps vs concurrent banking: in library
        # (sync) mode rpc handlers run on bus threads while drain_to
        # runs on the caller thread
        self._park_lock = threading.Lock()
        # (real, group) -> set of nodes holding members; exactly one of
        # them dispatches each message (per-message rotation in
        # shared_leader) — a group spanning nodes delivers exactly once
        # (emqx_shared_sub's cluster-wide mnesia member table)
        self._shared_nodes: Dict[Tuple[str, str], set] = {}
        # cached sorted candidate lists, invalidated on membership change
        self._shared_cands: Dict[Tuple[str, str], List[str]] = {}
        self._retainer = None  # set by attach_retainer (app mode)
        # topics touched by LIVE retain casts while a join-time bootstrap
        # is in flight: the (older) dump must not resurrect them
        self._retain_boot_seen: Optional[set] = None
        self._register_protos()
        self.membership.monitor(self._on_membership)
        bus.attach(name, self._handle)
        # the broker replicates routes / shared membership through this
        # node from now on (broker.subscribe/unsubscribe seam)
        self.broker.cluster = self
        self.broker.shared.leader_check = self.shared_leader

    # -- wiring ------------------------------------------------------------
    def _handle(self, from_node: str, payload):
        kind = payload[0]
        if kind == "membership":
            return self.membership.handle(from_node, payload)
        if kind == "rpc":
            if self._loop is not None and not self._loop.is_closed():
                import asyncio as _aio
                import concurrent.futures

                fut: concurrent.futures.Future = concurrent.futures.Future()

                def run():
                    try:
                        res = self.rpc.handle(from_node, payload)
                        # ASYNC handler (e.g. forward_batch's device
                        # dispatch): the reply — and thus the sender's
                        # QoS1 confirm — resolves only after the actual
                        # dispatch completes, while the loop stays free
                        if (
                            isinstance(res, tuple)
                            and len(res) == 2
                            and res[0] == "ok"
                            and _aio.iscoroutine(res[1])
                        ):
                            t = self._loop.create_task(res[1])

                            def done(t):
                                exc = (
                                    t.exception()
                                    if not t.cancelled()
                                    else _aio.CancelledError()
                                )
                                if exc:
                                    fut.set_exception(exc)
                                else:
                                    fut.set_result(("ok", t.result()))

                            t.add_done_callback(done)
                        else:
                            fut.set_result(res)
                    except BaseException as e:  # reply errors to caller
                        fut.set_exception(e)

                self._loop.call_soon_threadsafe(run)
                # generous: a forwarded batch can trigger a jit compile
                # (~10-40s cold) before the handler returns
                return fut.result(timeout=120)
            return self.rpc.handle(from_node, payload)
        return None

    def _register_protos(self) -> None:
        self.rpc.registry.register(
            "broker",
            1,
            {
                "forward": self._proto_forward,
                "forward_batch": self._proto_forward_batch,
            },
        )
        # v2: forwards known by (origin, incarnation, lane sequence), so
        # that a repeated group is answered and not dispatched again
        self.rpc.registry.register(
            "broker",
            2,
            {
                "forward": self._proto_forward,
                "forward_batch": self._proto_forward_batch,
                "forward_lane": self._proto_forward_lane,
            },
        )
        self.rpc.registry.register(
            "route",
            1,
            {
                "add_route": self.routes.add_route,
                "delete_route": self.routes.delete_route,
                "dump": self.routes.dump,
            },
        )
        # v2: one call carries a few thousand ordered (op, filter) pairs
        self.rpc.registry.register(
            "route",
            2,
            {
                "add_route": self.routes.add_route,
                "delete_route": self.routes.delete_route,
                "dump": self.routes.dump,
                "apply_batch": self._proto_route_apply_batch,
            },
        )
        self.rpc.registry.register(
            "cm",
            1,
            {
                "insert_channel": self._proto_insert_channel,
                "delete_channel": self._proto_delete_channel,
                "lookup_channel": self.lookup_channel,
                "discard": self._proto_discard,
            },
        )
        self.rpc.registry.register(
            "conf",
            1,
            {
                "append": self.conf_log.append,
                "receive_apply": self._proto_conf_receive_apply,
                "entries_after": self.conf_log.entries_after,
            },
        )
        self.rpc.registry.register(
            "shared",
            1,
            {
                "join": self._proto_shared_join,
                "leave": self._proto_shared_leave,
                "dump": self._proto_shared_dump,
            },
        )
        self.rpc.registry.register(
            "shard",
            1,
            {
                "advertise": self._proto_shard_advertise,
                "dump": self.shards.dump,
            },
        )
        self.rpc.registry.register(
            "retain",
            1,
            {
                "store": self._proto_retain_store,
                "dump": self._proto_retain_dump,
            },
        )
        # v2 adds the PAGED bootstrap read (a 5-10M retained store must
        # not ship as one multi-GB RPC reply); v1 stays frozen for
        # old-version peers (BPAPI evolution rules)
        self.rpc.registry.register(
            "retain",
            2,
            {
                "store": self._proto_retain_store,
                "dump": self._proto_retain_dump,
                "dump_page": self._proto_retain_dump_page,
            },
        )
        self.rpc.registry.register(
            "sess",
            1,
            {
                "insert_parked": self._proto_insert_parked,
                "delete_parked": self._proto_delete_parked,
                "resume_begin": self._proto_resume_begin,
                "resume_end": self._proto_resume_end,
                "dump_parked": self._proto_dump_parked,
            },
        )
        # v2 adds the drain/rolling-upgrade handoff (BPAPI discipline:
        # v1 is frozen, new behavior = new version carrying the union)
        self.rpc.registry.register(
            "sess",
            2,
            {
                "insert_parked": self._proto_insert_parked,
                "delete_parked": self._proto_delete_parked,
                "resume_begin": self._proto_resume_begin,
                "resume_end": self._proto_resume_end,
                "dump_parked": self._proto_dump_parked,
                "park_remote": self._proto_park_remote,
                "park_append": self._proto_park_append,
            },
        )

    def _on_membership(self, event: str, node: str) -> None:
        if event == "node_down":
            # sessions parked on a dead node are unreachable until it
            # returns: purge the owner entries so reconnecting clients get
            # fresh sessions instead of resume limbo (route-GC semantics)
            gone = [
                cid for cid, n in self._parked_owner.items() if n == node
            ]
            for cid in gone:
                self._parked_owner.pop(cid, None)
            purged = self.routes.cleanup_node(node)
            with self._chan_lock:
                for cid, (n, _) in list(self._channels.items()):
                    if n == node:
                        del self._channels[cid]
            self.rpc.forget_peer(node)
            # shared-group leadership: a dead node's members are gone;
            # surviving member nodes take over dispatch
            for key, nodes in list(self._shared_nodes.items()):
                nodes.discard(node)
                if not nodes:
                    self._shared_nodes.pop(key, None)
            self._shared_cands.clear()
            # shard re-own rides the same degrade ladder that declared
            # the node dead (heartbeat expiry / open breakers): the dead
            # owner's mesh slices move to rendezvous-chosen survivors,
            # so forwards reroute to a live slice instead of stalling
            # behind the dead peer's send deadline (docs/scale_out.md)
            moves = self.shards.reown(
                node, self.membership.running_nodes()
            )
            if moves:
                import logging

                logging.getLogger("emqx_tpu.cluster").warning(
                    "node %s down: re-owned shards %s", node, moves
                )
            self.broker.metrics.inc("cluster.nodedown.routes_purged", purged)
        elif event == "node_up":
            self.rpc.forget_peer(node)  # re-negotiate BPAPI versions

    # -- lifecycle ---------------------------------------------------------
    def join(self, seed: str) -> bool:
        """Join the cluster: membership, route bootstrap, conf catch-up."""
        if not self.membership.join(seed):
            return False
        # pull the seed's route replica (mria replicant catch-up)
        self.routes.load(self.rpc.call(seed, "route", "dump"))
        # push our own local routes to everyone, batched like live ones
        mine = self.routes.local_filters()
        for peer in self.membership.peers():
            self._lane_put("route", peer, *(("add", f) for f in mine))
        # config log catch-up
        entries = self.rpc.call(seed, "conf", "entries_after", self.conf_log.cursor)
        self.conf_log.catch_up_from([tuple(e) for e in entries])
        # parked-session owner map bootstrap (a late joiner must be able
        # to resume sessions parked before it joined)
        self._parked_owner.update(
            self.rpc.call(seed, "sess", "dump_parked")
        )
        # mesh-shard ownership bootstrap + (re-)announce our own slice:
        # a returning owner reclaims its home shards here (the
        # advertisement IS the reclaim — see ShardOwnership.advertise)
        try:
            if self.rpc.supported_version(seed, "shard") >= 1:
                self.shards.load(self.rpc.call(seed, "shard", "dump"))
                mine = self.shards.local_shards()
                if mine:
                    self._shard_cast()
        except RpcError:
            pass  # pre-shard-proto seed: ownership stays local-only
        # shared-group membership bootstrap + announce our own groups
        for r, g, nodes in self.rpc.call(seed, "shared", "dump"):
            self._shared_nodes.setdefault((r, g), set()).update(nodes)
            self._shared_cands.pop((r, g), None)
        for real, groups in self.broker.shared._table.items():
            for gname in groups:
                self.shared_join(real, gname)
        # retained-store bootstrap, both directions (late joiner catches
        # up on the seed's set; its own pre-join retained pushes out like
        # routes do). The dump applies ON THE LOOP in app mode — the
        # retainer trie has no lock, and live casts are already
        # loop-marshalled; `_retain_boot_seen` stops the older dump from
        # resurrecting a topic a concurrent live cast just set/cleared.
        if self._retainer is not None:
            self._retain_boot_seen = set()
            try:

                def apply_page(page):
                    seen = self._retain_boot_seen or set()
                    for mjson in page:
                        if mjson.get("topic") not in seen:
                            self._proto_retain_store(mjson)

                # the local pre-join snapshot is taken ON THE LOOP (and
                # BEFORE any page applies, so the seed's own set never
                # re-replicates back out): the retainer trie has no lock
                # and listeners already serve during join retries — an
                # executor-thread walk could tear mid-mutation
                local = self._call_on_loop(self._retainer.all_messages)
                if self.rpc.supported_version(seed, "retain") >= 2:
                    # paged bootstrap: bounded pages instead of one
                    # multi-GB reply at 5-10M retained messages; each
                    # page applies on the loop before the next is pulled
                    cursor = None
                    while True:
                        page, cursor = self.rpc.call(
                            seed, "retain", "dump_page", cursor,
                            self.RETAIN_PAGE_MAX,
                        )
                        self._call_on_loop(lambda p=page: apply_page(p))
                        if cursor is None:
                            break
                else:
                    dump = self.rpc.call(seed, "retain", "dump")
                    self._call_on_loop(lambda: apply_page(dump))
                for m in local:
                    self._replicate_retain(m)
            except RpcError as e:
                import logging

                logging.getLogger("emqx_tpu.cluster").warning(
                    "retained bootstrap from %s failed: %s", seed, e
                )
                self.broker.metrics.inc("cluster.retain.bootstrap_failed")
            finally:
                self._retain_boot_seen = None
        return True

    def _call_on_loop(self, fn, timeout: float = 120.0):
        """Run `fn` on the app event loop (when one is attached) from a
        bus/executor thread; synchronous fallback in library mode."""
        if self._loop is None or self._loop.is_closed():
            return fn()
        import concurrent.futures

        fut: "concurrent.futures.Future" = concurrent.futures.Future()

        def run():
            try:
                fut.set_result(fn())
            except BaseException as e:
                fut.set_exception(e)

        self._loop.call_soon_threadsafe(run)
        return fut.result(timeout=timeout)

    def leave(self) -> None:
        # the pool REFERENCES are construction-only (CX discipline):
        # shutdown() flips state inside the executors themselves, so a
        # loop-side submit racing this drain (leave runs on the default
        # executor during a rolling-upgrade handoff) gets a RuntimeError
        # that `_pool_submit` drops — never a torn None dereference
        with self._lanes_lock:
            self._leaving = True  # a lane stops retrying a peer
        if self._repl_pool is not None:
            self._repl_pool.shutdown(wait=True)  # flush pending replication
        if self._fwd_pool is not None:
            self._fwd_pool.shutdown(wait=True)  # flush the lanes
        self.membership.leave()
        self.rpc.stop()
        self.bus.detach(self.name)

    @staticmethod
    def _pool_submit(pool, fn, *args) -> None:
        """Submit replication/forward work to an app-mode pool. A pool
        already shut down by a racing leave() swallows the task — the
        bus is detaching, the work has nowhere to go."""
        try:
            pool.submit(fn, *args)
        except RuntimeError:
            pass

    # -- subscribe side ----------------------------------------------------
    def subscribe(
        self,
        sid: str,
        client_id: str,
        filter_: str,
        opts: pkt.SubOpts,
        deliver,
    ) -> None:
        """Route replication + shared membership happen inside the broker
        seam (broker.cluster points back here), so library callers and
        the live app share one code path."""
        self.broker.subscribe(sid, client_id, filter_, opts, deliver)

    def unsubscribe(self, sid: str, filter_: str) -> bool:
        return self.broker.unsubscribe(sid, filter_)

    def _replicate_add(self, filter_: str) -> None:
        self.routes.add_route(filter_, self.name)
        self._replicate("add", filter_)

    def _replicate_delete(self, filter_: str) -> None:
        self.routes.delete_route(filter_, self.name)
        self._replicate("delete", filter_)

    def _replicate(self, op: str, filter_: str) -> None:
        """A local route change goes, in order, into every peer's route
        lane; the lane ships what has gathered as one `route` v2
        `apply_batch` per drain (per filter to a v1 peer: wildcards as
        confirmed calls, maybe_trans, emqx_router.erl:118-121, exact
        topics as ordered casts). The event loop never waits for a peer;
        without a loop the lane drains right here, so a library caller
        finds the peers' replicas written when `subscribe` returns."""
        item = (op, filter_)
        for p in self.membership.peers():
            self._lane_put("route", p, item)

    # -- the lanes -----------------------------------------------------------
    def _lane_put(self, kind: str, peer: str, *items) -> None:
        key = (kind, peer)
        lane = self._lanes.get(key)  # lint: disable=LK001
        if lane is None:
            with self._lanes_lock:
                lane = self._lanes.setdefault(key, _Lane())
        with lane.lock:
            lane.items.extend(items)
            start = not lane.running
            lane.running = True
        if not start:
            return
        if self._fwd_pool is None:
            self._lane_run(kind, peer, lane)
        else:
            self._pool_submit(self._fwd_pool, self._lane_run, kind, peer, lane)

    def _lane_run(self, kind: str, peer: str, lane: _Lane) -> None:
        while True:
            with lane.lock:
                if not lane.items:
                    lane.running = False
                    return
                if kind == "route":
                    group = [
                        lane.items.popleft()
                        for _ in range(min(len(lane.items), ROUTE_BATCH_MAX))
                    ]
                else:  # whole batches, joined up to FWD_GROUP_MAX messages
                    group = [lane.items.popleft()]
                    n = len(group[0][0])
                    while lane.items and n + len(
                        lane.items[0][0]
                    ) <= FWD_GROUP_MAX:
                        group.append(lane.items.popleft())
                        n += len(group[-1][0])
                    first = lane.seq + 1
                    lane.seq += len(group)
            try:
                if kind == "route":
                    self._ship_routes(peer, group)
                else:
                    self._ship_forwards(peer, first, group)
            except Exception:  # noqa: BLE001 — a lane outlives a bad group
                log.exception("%s lane to %s: group dropped", kind, peer)

    def _lane_call(self, peer: str, send, retried: Optional[str] = None):
        """One confirmed call of a lane, `send(patient)`. The reply of a
        request that went out is waited for while the peer is alive by
        `Membership` (no second send while the first may still be
        applied); a connection
        that broke, or a peer that cannot be reached, is tried again with
        backoff, for as long as the peer is alive. Only `node_down` (or
        leaving, or library mode, whose caller cannot wait) gives up:
        RpcError."""
        def patient() -> bool:
            return (
                not self._leaving  # lint: disable=LK001
                and self.membership.is_alive(peer)
            )

        delay = 0.05
        while True:
            try:
                return send(patient)
            except RpcError:
                if self._fwd_pool is None or not patient():
                    raise
                if retried is not None:
                    self.broker.metrics.inc(retried)
                time.sleep(delay)
                delay = min(delay * 2.0, 1.0)

    def _ship_routes(self, peer: str, ops: List[Tuple[str, str]]) -> None:
        def send(patient):
            if self.rpc.supported_version(peer, "route") >= 2:
                return self.rpc.call_on(
                    peer, "route", "apply_batch", (self.name, ops),
                    CH_ROUTE, patient,
                )
            for op, filter_ in ops:  # a v1 peer: filter by filter
                rpc_send = (
                    self.rpc.call if T.wildcard(filter_) else self.rpc.cast
                )
                if op == "add":
                    rpc_send(peer, "route", "add_route", filter_, self.name)
                else:
                    rpc_send(
                        peer, "route", "delete_route", filter_, self.name
                    )

        try:
            self._lane_call(peer, send)
        except RpcError:
            pass  # peer down: membership GC will reconcile

    def _proto_route_apply_batch(self, node: str, ops) -> object:
        """Inbound `route` v2: `node`'s ordered (op, filter) pairs. On a
        live app the batch is applied in slices of the event loop, each
        one take of the replica's lock and about ROUTE_SLICE_S long, with
        the loop's other work (SUBSCRIBEs, publishes) between two."""
        m = self.broker.metrics
        m.inc("cluster.route.batches")
        m.inc("cluster.route.ops", len(ops))
        if self._loop is not None and not self._loop.is_closed():
            return self._aapply_routes(node, ops)
        with _prof.section("cluster.route.apply"):
            self.routes.apply_batch(ops, node)
        return len(ops)

    async def _aapply_routes(self, node: str, ops) -> int:
        i = 0
        while i < len(ops):
            n = self._route_slice
            with _prof.section("cluster.route.apply") as sec:
                self.routes.apply_batch(ops[i:i + n], node)
            i += n
            if sec.seconds > 2.0 * ROUTE_SLICE_S:
                self._route_slice = max(ROUTE_SLICE_MIN, n // 2)
            elif sec.seconds < 0.5 * ROUTE_SLICE_S:
                self._route_slice = min(ROUTE_SLICE_MAX, n * 2)
            await asyncio.sleep(0)
        return len(ops)

    # -- mesh-shard ownership (scale-out serving) --------------------------
    def attach_mesh_slice(
        self, mesh_shape, index: int = 0, total: int = 1
    ) -> List[str]:
        """Declare this node's slice of the global subscriber-lane
        space: slice `index` of `total`, served by a local mesh of
        `mesh_shape` = (dp, tp). Advertised to every current peer (late
        joiners pull the dump). The serving engine's span label
        (`router.device_step` shard attr) follows the advertisement."""
        shards = self.shards.advertise_local(
            tuple(mesh_shape), index, total
        )
        self.broker.shard_label = self.shards.label()
        dev = self.broker._device
        if dev is not None and hasattr(dev, "shard_label"):
            dev.shard_label = self.broker.shard_label
        self._shard_cast()
        return shards

    def _shard_cast(self) -> None:
        mine = self.shards.local_shards()
        if not mine:
            return
        shape = list(
            self.shards._home.get(self.name, ((), (0, 0)))[1]
        )

        def one(p):
            self.rpc.cast(
                p, "shard", "advertise", self.name, mine, shape,
                key="shard",
            )

        for p in self.membership.peers():
            if self._repl_pool is not None:
                self._pool_submit(self._repl_pool, one, p)
            else:
                one(p)

    def _proto_shard_advertise(self, node: str, shards, shape) -> None:
        self.shards.advertise(node, list(shards), tuple(shape))

    def _live_dest(self, node: str) -> str:
        """Remap a publish destination whose owner is DOWN to the node
        that re-owned its shard (rendezvous successor). While membership
        still believes the owner is alive — or no successor exists —
        the original destination stands and the send path's breaker/
        retry ladder handles it."""
        if node == self.name or self.membership.is_alive(node):
            return node
        alt = self.shards.successor_node(node)
        if alt is not None and alt != node:
            self.broker.metrics.inc("mesh.shard.reroutes")
            return alt
        return node

    # -- publish side ------------------------------------------------------
    def publish(self, msg: Message) -> int:
        """Cluster publish: match once, dispatch local, forward per node."""
        rec = getattr(self.broker, "spans", None)
        sp = rec.publish_begin(msg) if rec is not None else None
        msg = self.broker.hooks.run_fold("message.publish", (), msg)
        if msg is None or msg.headers.get("allow_publish") is False:
            self.broker.metrics.inc("messages.dropped")
            if sp is not None:
                rec.finish_span(sp, 0, status="error")
            return 0
        dests = self.routes.match_dests(msg.topic)
        n = self._dispatch_dests(msg, dests)
        if sp is not None:
            rec.finish_span(sp, n)
        return n

    def publish_batch(self, msgs: Sequence[Message]) -> int:
        """One route-table match kernel for the whole batch, then fan out.

        Remote fan-out is batched per destination node: a single
        forward_batch per (batch, node) instead of per (message, node) —
        the batching the TPU design adds over the reference hot path.
        """
        kept: List[Message] = []
        for m in msgs:
            m = self.broker.hooks.run_fold("message.publish", (), m)
            if m is not None and m.headers.get("allow_publish") is not False:
                kept.append(m)
        all_dests = self.routes.match_dests_batch([m.topic for m in kept])
        total = 0
        per_node: Dict[str, List[Tuple[Message, List[str]]]] = {}
        for m, dests in zip(kept, all_dests):
            for node, filters in dests.items():
                node = self._live_dest(node)
                if node == self.name:
                    total += self.broker.dispatch(filters, m)
                else:
                    per_node.setdefault(node, []).append((m, filters))
        for node, batch in per_node.items():
            self.rpc.cast(node, "broker", "forward_batch", batch, key=node)
            total += sum(1 for _ in batch)
        return total

    # -- cluster-wide retained store ---------------------------------------
    def attach_retainer(self, retainer, hooks) -> None:
        """Replicate the retained store cluster-wide (the reference's
        retainer rides a replicated mnesia table, emqx_retainer_mnesia;
        here retained set/clear ops ride ordered casts and a join-time
        bootstrap): a subscriber on ANY node replays retained messages
        published on any other."""
        self._retainer = retainer

        def on_pub(msg):
            if (
                msg is not None
                and msg.retain
                and not msg.headers.get("retain_replicated")
            ):
                self._replicate_retain(msg)
            return None

        # priority below the retainer's own store hook: replicate what
        # was actually accepted locally
        hooks.add("message.publish", on_pub, priority=90,
                  tag="cluster.retain_replicate")

    def _replicate_retain(self, msg: Message) -> None:
        from emqx_tpu.storage.codec import msg_to_json

        mjson = msg_to_json(msg)

        def one(p):
            self.rpc.cast(p, "retain", "store", mjson, key=msg.topic)

        for p in self.membership.peers():
            if self._repl_pool is not None:
                self._pool_submit(self._repl_pool, one, p)
            else:
                one(p)

    RETAIN_DUMP_CAP = 100_000

    def _proto_retain_store(self, mjson) -> None:
        if self._retainer is None:
            return
        msg = self._msg_from(mjson)
        if self._retain_boot_seen is not None:
            # a live cast during OUR bootstrap window: the dump snapshot
            # is older than this op and must not override it
            self._retain_boot_seen.add(msg.topic)
        # straight into the store — NOT the publish fold — so replicas
        # never re-replicate or re-dispatch (empty payload = clear, the
        # same MQTT semantics on_publish already implements)
        msg.headers["retain_replicated"] = True
        self._retainer.on_publish(msg)

    def _proto_retain_dump(self):
        """LEGACY (retain v1) join-time bootstrap: the seed's retained
        set in one reply, capped. v2 peers use the paged read."""
        from emqx_tpu.storage.codec import msg_to_json

        if self._retainer is None:
            return []
        msgs = self._retainer.all_messages(limit=self.RETAIN_DUMP_CAP + 1)
        if len(msgs) > self.RETAIN_DUMP_CAP:
            self.broker.metrics.inc("cluster.retain.dump_truncated")
            msgs = msgs[: self.RETAIN_DUMP_CAP]
        return [msg_to_json(m) for m in msgs]

    RETAIN_PAGE_MAX = 5000

    def _proto_retain_dump_page(self, after, limit):
        """Paged bootstrap read (retain v2): ordered cursor walk, each
        page a bounded RPC reply — a 5-10M-message store bootstraps in
        bounded memory (emqx_retainer_mnesia.erl:146-152 paged-read
        parity). Returns (page_json, next_cursor | None)."""
        from emqx_tpu.storage.codec import msg_to_json

        if self._retainer is None:
            return [], None
        msgs, nxt = self._retainer.messages_page(
            after, min(int(limit), self.RETAIN_PAGE_MAX)
        )
        return [msg_to_json(m) for m in msgs], nxt

    # -- cluster-wide shared groups ----------------------------------------
    def shared_join(self, real: str, group: str) -> None:
        """First local member of (real, group): announce membership so
        every node agrees on the group leader."""
        self._shared_nodes.setdefault((real, group), set()).add(self.name)
        self._shared_cands.pop((real, group), None)
        self._shared_cast("join", real, group)

    def shared_leave(self, real: str, group: str) -> None:
        self._proto_shared_leave(real, group, self.name)
        self._shared_cast("leave", real, group)

    def _shared_cast(self, method: str, real: str, group: str) -> None:
        def one(p):
            self.rpc.cast(p, "shared", method, real, group, self.name,
                          key=real)

        for p in self.membership.peers():
            if self._repl_pool is not None:
                self._pool_submit(self._repl_pool, one, p)
            else:
                one(p)

    def shared_leader(self, real: str, group: str, msg=None) -> bool:
        """Pick the dispatching node for (real, group) per MESSAGE
        across the cluster-wide member-node set. Every member node holds
        the message already (route forwarding), so rotating the
        dispatcher balances the group across nodes with no extra RPC —
        the reference picks among cluster-wide members the same way
        (emqx_shared_sub.erl:234-285). Hash strategies stay keyed (same
        client/topic -> same node -> same member); sticky keeps a single
        dispatching node so the group genuinely sticks to one member.
        A local group not yet announced (race) defaults to dispatching —
        transient dup beats transient loss."""
        s = self._shared_nodes.get((real, group))
        if not s:
            return True
        # dispatch only asks when local members exist; the sorted
        # candidate list is cached per group (per-message sorting would
        # tax the hot path) and invalidated on membership changes
        cands = self._shared_cands.get((real, group))
        if cands is None:
            cands = sorted(set(s) | {self.name})
            self._shared_cands[(real, group)] = cands
        if len(cands) == 1:
            return True
        strategy = self.broker.shared.strategy
        if strategy == "sticky" or msg is None:
            return self.name == cands[0]
        if strategy == "hash_clientid":
            key = stable_hash(msg.from_client)
        elif strategy == "hash_topic":
            key = stable_hash(msg.topic)
        else:  # random / round_robin: rotate per message (mid is
            # GUID-stable across the forward path, so all member nodes
            # agree on the same dispatcher)
            key = stable_hash(f"{msg.from_client}|{msg.mid}")
        return self.name == cands[key % len(cands)]

    def _proto_shared_join(self, real: str, group: str, node: str) -> None:
        self._shared_nodes.setdefault((real, group), set()).add(node)
        self._shared_cands.pop((real, group), None)

    def _proto_shared_leave(self, real: str, group: str, node: str) -> None:
        s = self._shared_nodes.get((real, group))
        if s is not None:
            s.discard(node)
            if not s:
                self._shared_nodes.pop((real, group), None)
        self._shared_cands.pop((real, group), None)

    def _proto_shared_dump(self):
        return [
            (r, g, sorted(nodes))
            for (r, g), nodes in self._shared_nodes.items()
        ]

    def forward_batch_remote(self, msgs: Sequence[Message]) -> List[int]:
        """Forward already-locally-dispatched messages to their REMOTE
        route owners — the publish half the app's broker delegates here
        when cluster mode is on (local dispatch stays on the device batch
        path; this adds one batch per destination node).
        Returns per-message remote destination counts.

        Every batch goes into its destination's forward lane and leaves
        as a confirmed call known by (this node, its incarnation, the
        lane's sequence number): the receiver dispatches a group once and
        answers a repeat with the first one's result. The event loop
        never waits for a peer. With `forward_mode` "sync" (config
        `cluster.rpc_mode`, the reference's `[rpc, mode]`) the batch's
        confirmation is kept for the settle that takes it
        (`take_confirms`): the publishers' PUBACKs wait for it. A batch
        is given up only with its destination down: it then goes to the
        shard's successor, or counts in messages.forward.failed."""
        _prof.begin("cluster.forward.out")
        handed = 0
        try:
            all_dests = self.routes.match_dests_batch(
                [m.topic for m in msgs]
            )
            out = [0] * len(msgs)
            per_node: Dict[str, List[Message]] = {}
            for i, (m, dests) in enumerate(zip(msgs, all_dests)):
                for node in dests:
                    # a dest whose owner died reroutes to the shard's
                    # rendezvous successor; a successor that is US needs
                    # no forward (local dispatch already ran on this batch)
                    node = self._live_dest(node)
                    if node == self.name:
                        continue
                    per_node.setdefault(node, []).append(m)
                    out[i] += 1
            if not per_node:
                return out

            # span-context propagation is free — the `traceparent` header
            # rides inside the pickled Message — but the hop itself is
            # worth a span: record where each sampled trace LEFT this node
            rec = getattr(self.broker, "spans", None)
            if rec is not None:
                for node, batch in per_node.items():
                    for m in batch:
                        rec.forward(m, node)

            handed = sum(out)
            metrics = self.broker.metrics
            metrics.inc("cluster.forward.batches", len(per_node))
            metrics.inc("cluster.forward.messages", handed)
            self._unconfirmed_add(handed)
            confirm = (
                _Confirm(len(per_node))
                if self.forward_mode == "sync"
                else None
            )
            now = time.perf_counter()
            for node, batch in per_node.items():
                self._lane_put("fwd", node, (batch, now, confirm))
            if confirm is not None and not confirm.future.done():
                if len(self._confirms) >= 64:  # nobody settles: prune
                    self._confirms = [
                        c for c in self._confirms if not c.future.done()
                    ]
                self._confirms.append(confirm)
            return out
        finally:
            _prof.end(handed)

    def take_confirms(self) -> List["concurrent.futures.Future"]:
        """The unresolved confirmations of the forwards handed off since
        the last take (`rpc_mode: sync`; the settle of an ingest batch
        resolves its publishers once these are done)."""
        if not self._confirms:
            return []
        taken, self._confirms = self._confirms, []
        return [c.future for c in taken if not c.future.done()]

    def _unconfirmed_add(self, n: int) -> None:
        with self._lanes_lock:
            self._unconfirmed += n
            now = self._unconfirmed
        self.broker.metrics.gauge_set("cluster.forward.unconfirmed", now)

    def _ship_forwards(self, peer: str, first: int, group) -> None:
        """One group of a forward lane: `group` is [(msgs, t_handoff,
        confirm)], numbered `first`... in the lane's sequence."""
        metrics = self.broker.metrics
        n = sum(len(msgs) for msgs, _, _ in group)
        ok = False
        args = (self.name, self._epoch, first,
                [msgs for msgs, _, _ in group])

        def send(patient):
            # (the version handshake is part of what is tried again: an
            # alive peer whose loop is too busy to announce is no dead one)
            if self.rpc.supported_version(peer, "broker") >= 2:
                return self.rpc.call_on(
                    peer, "broker", "forward_lane", args, CH_FORWARD, patient
                )
            for msgs, _, _ in group:  # a v1 peer: no sequence numbers
                self.rpc.call(
                    peer, "broker", "forward_batch", [(m, ()) for m in msgs]
                )

        try:
            self._lane_call(peer, send, retried="cluster.forward.retries")
            ok = True
        except RpcError:
            # the destination is down (or, in library mode, did not
            # answer the one attempt): its shard's successor, else lost
            alt = self._live_dest(peer)
            if alt != peer and alt != self.name:
                metrics.inc("cluster.forward.retries")
                self._lane_put("fwd", alt, *group)
                return
            metrics.inc("messages.forward.failed", n)
        except Exception:  # noqa: BLE001 — the remote dispatch raised
            log.exception("forward to %s failed there", peer)
            metrics.inc("messages.forward.failed", n)
        now = time.perf_counter()
        for _, t0, confirm in group:
            if ok:
                metrics.observe("cluster.forward.confirm.seconds", now - t0)
            if confirm is not None:
                confirm.one_done()
        self._unconfirmed_add(-n)

    def _dispatch_dests(self, msg: Message, dests: Dict[str, List[str]]) -> int:
        n = 0
        if not dests:
            self.broker.hooks.run("message.dropped", msg, "no_subscribers")
            return 0
        rec = getattr(self.broker, "spans", None)
        for node, filters in dests.items():  # aggre: one entry per node
            node = self._live_dest(node)
            if node == self.name:
                n += self.broker.dispatch(filters, msg)
            else:
                if rec is not None:
                    rec.forward(msg, node)
                if self.forward_mode == "sync" or msg.qos > 0:
                    try:
                        n += self.rpc.call(
                            node, "broker", "forward", msg, filters
                        )
                    except RpcError:
                        self.broker.metrics.inc("messages.forward.failed")
                else:
                    self.rpc.cast(
                        node, "broker", "forward", msg, filters, key=msg.topic
                    )
                    n += 1  # async: assumed delivered (gen_rpc cast)
        return n

    def _proto_forward(self, msg: Message, filters: List[str]) -> int:
        return self.broker.dispatch(filters, msg)

    def _proto_forward_batch(self, batch) -> int:
        """Inbound batched forward: ride the broker's device batch path
        (re-match + bitmap fan-out on the receiving node's own mirror,
        emqx_broker.erl:278-293 forward -> dispatch). Small batches fall
        through to the per-message host dispatch inside
        dispatch_batch_folded itself."""
        msgs = [m for m, _fs in batch]
        # forward=False: this IS the receiving half — re-forwarding here
        # would cascade batches between route owners forever
        # (same gate as the _handle marshal: a CLOSED loop must take the
        # sync path, or the reply would carry a never-awaited coroutine)
        if self._loop is not None and not self._loop.is_closed():
            # app mode: return a coroutine — the rpc marshal resolves the
            # reply when the dispatch ACTUALLY completes (QoS1 confirm =
            # delivered/banked) while any kernel launch/compile runs in
            # an executor thread, keeping the event loop free
            return self._afwd(msgs)
        return sum(self.broker.dispatch_batch_folded(msgs, forward=False))

    async def _afwd(self, msgs) -> int:
        res = await self.broker.adispatch_batch_folded(msgs, forward=False)
        return sum(res)

    def _proto_forward_lane(self, origin: str, epoch: str, first: int,
                            batches) -> object:
        """Inbound `broker` v2: the batches `first`, `first`+1, ... of
        `origin`'s forward lane to this node. Applied exactly once: a
        group that was applied is answered with its result, one that is
        being applied is waited for, and neither is dispatched again
        (`cluster.forward.duplicates`). The reply leaves after the
        dispatch, so it is the confirmation `rpc_mode: sync` waits for."""
        with _prof.section("cluster.forward.in") as sec:
            msgs: List[Message] = []
            last = first + len(batches) - 1
            with self._fwd_in_lock:
                st = self._fwd_in.get(origin)
                if st is None or st["epoch"] != epoch:
                    st = self._fwd_in[origin] = {
                        "epoch": epoch, "done": 0, "result": 0,
                        "running": None,
                    }
                running = st["running"]
                if running is not None and running[0] == first:
                    fut, repeat = running[1], True  # still being applied
                elif last <= st["done"]:
                    fut, repeat = None, True  # applied: its result
                else:
                    fut, repeat = concurrent.futures.Future(), False
                    st["running"] = (first, fut)
                    msgs = [m for b in batches for m in b]
                result = st["result"]
            sec.n = len(msgs)
        on_loop = self._loop is not None and not self._loop.is_closed()
        if repeat:
            self.broker.metrics.inc(
                "cluster.forward.duplicates", len(batches)
            )
            if fut is None:
                return result
            return self._await_fwd(fut) if on_loop else fut.result()
        if on_loop:
            return self._afwd_lane(st, last, fut, msgs)
        try:
            n = sum(self.broker.dispatch_batch_folded(msgs, forward=False))
        except BaseException as e:
            self._fwd_failed(st, fut, e)
            raise
        return self._fwd_done(st, last, fut, n)

    @staticmethod
    async def _await_fwd(fut) -> int:
        return await asyncio.wrap_future(fut)

    def _fwd_failed(self, st: dict, fut, exc) -> None:
        with self._fwd_in_lock:
            st["running"] = None  # not applied: a repeat dispatches
        fut.set_exception(exc)

    def _fwd_done(self, st: dict, last: int, fut, n: int) -> int:
        with self._fwd_in_lock:
            st["done"], st["result"], st["running"] = last, n, None
        fut.set_result(n)
        return n

    async def _afwd_lane(self, st: dict, last: int, fut, msgs) -> int:
        try:
            n = await self._afwd(msgs)
        except BaseException as e:
            self._fwd_failed(st, fut, e)
            raise
        return self._fwd_done(st, last, fut, n)

    # -- channel registry (emqx_cm_registry parity) ------------------------
    def register_channel(self, client_id: str, sid: str) -> None:
        with self._chan_lock:
            self._channels[client_id] = (self.name, sid)
        for p in self.membership.peers():
            self.rpc.cast(
                p, "cm", "insert_channel", client_id, self.name, sid,
                key=client_id,
            )

    def unregister_channel(self, client_id: str) -> None:
        with self._chan_lock:
            self._channels.pop(client_id, None)
        for p in self.membership.peers():
            self.rpc.cast(
                p, "cm", "delete_channel", client_id, self.name, key=client_id
            )

    def lookup_channel(self, client_id: str) -> Optional[Tuple[str, str]]:
        with self._chan_lock:
            v = self._channels.get(client_id)
        return tuple(v) if v else None

    def discard_session(self, client_id: str) -> bool:
        """Cluster-wide discard of an existing channel (emqx_cm.erl:245-273)."""
        found = self.lookup_channel(client_id)
        if not found:
            return False
        node, sid = found
        if node == self.name:
            return self._proto_discard(client_id)
        try:
            return self.rpc.call(node, "cm", "discard", client_id)
        except RpcError:
            return False

    def _proto_insert_channel(self, client_id: str, node: str, sid: str):
        with self._chan_lock:
            self._channels[client_id] = (node, sid)

    def _proto_delete_channel(self, client_id: str, node: str):
        with self._chan_lock:
            cur = self._channels.get(client_id)
            if cur and cur[0] == node:
                del self._channels[client_id]

    def _proto_discard(self, client_id: str) -> bool:
        """Drop the local channel's subscriptions + registry entry."""
        found = self.lookup_channel(client_id)
        if not found or found[0] != self.name:
            return False
        _, sid = found
        for cid, f, _ in list(self.broker.subscriptions()):
            if cid == client_id:
                self.unsubscribe(sid, f)
        self.unregister_channel(client_id)
        return True

    # -- persistent-session park/resume (emqx_session_router parity) -------
    def park_session(self, client_id: str, session_json: Dict, deadline: float) -> None:
        """Park a detached persistent session on this node: its wildcard/
        plain routes stay HERE (the separate persistent-session route
        table, emqx_session_router.erl), and matched messages bank in the
        park's pending list until a resume fetches them."""
        from emqx_tpu.mqtt import packet as pkt
        from emqx_tpu.storage.codec import msg_to_json, subopts_from_json

        park = {
            "session": session_json,
            "deadline": deadline,
            "pending": [],
            "marker": None,  # set by resume_begin: forward-to-node marker
        }
        self._parked[client_id] = park
        sid = f"parked:{client_id}"

        def deliver(msg: Message, opts: pkt.SubOpts) -> None:
            qos = min(msg.qos, opts.qos)
            if qos == 0:
                return
            with self._park_lock:
                park["pending"].append(msg_to_json(msg))

        for f, opts_json in session_json.get("subscriptions", {}).items():
            self.subscribe(sid, client_id, f, subopts_from_json(opts_json), deliver)
        self._parked_owner[client_id] = self.name
        for p in self.membership.peers():
            self.rpc.cast(p, "sess", "insert_parked", client_id, self.name)

    def resume_session(self, client_id: str, install=None):
        """Two-phase cross-node resume (emqx_session_router.erl:171-220
        resume_begin/resume_end with markers):

        1. resume_begin on the owner: returns the session snapshot + the
           pendings banked so far; the owner sets a marker and KEEPS
           routing, so messages arriving during the handoff keep banking.
        2. `install(session_json)` runs HERE, between the phases — the
           caller sets up its local routes for the session while the
           owner's park still catches in-flight traffic; only then
        3. resume_end on the owner returns the straggler pendings that
           arrived during the window and drops the park + its routes.

        Without an installed local route before resume_end, a message
        landing in the gap would match no route — the exact loss the
        marker protocol exists to prevent.

        Returns (session_json, pending_msgs) or None when no parked
        session exists anywhere.
        """
        owner = self._parked_owner.get(client_id)
        if owner is None:
            return None
        if owner == self.name:
            begin = self._proto_resume_begin(client_id, self.name)
        else:
            try:
                begin = self.rpc.call(
                    owner, "sess", "resume_begin", client_id, self.name
                )
            except RpcError:
                self._parked_owner.pop(client_id, None)
                return None
        if begin is None:
            return None
        snap, pending = begin
        if install is not None:
            install(snap)  # local routes live BEFORE the park is dropped
        if owner == self.name:
            stragglers = self._proto_resume_end(client_id)
        else:
            stragglers = self.rpc.call(owner, "sess", "resume_end", client_id)
        return snap, [
            self._msg_from(m) for m in list(pending) + list(stragglers)
        ]

    @staticmethod
    def _msg_from(m):
        from emqx_tpu.storage.codec import msg_from_json

        return msg_from_json(m)

    def _proto_insert_parked(self, client_id: str, node: str) -> None:
        self._parked_owner[client_id] = node

    def _proto_delete_parked(self, client_id: str) -> None:
        self._parked_owner.pop(client_id, None)

    def _proto_dump_parked(self) -> Dict[str, str]:
        return dict(self._parked_owner)

    def _proto_resume_begin(self, client_id: str, to_node: str):
        park = self._parked.get(client_id)
        if park is None:
            return None
        park["marker"] = to_node
        with self._park_lock:
            pending, park["pending"] = park["pending"], []
        return park["session"], pending

    def _proto_resume_end(self, client_id: str):
        park = self._parked.pop(client_id, None)
        if park is None:
            return []
        sid = f"parked:{client_id}"
        for f in park["session"].get("subscriptions", {}):
            self.unsubscribe(sid, f)
        self._parked_owner.pop(client_id, None)
        for p in self.membership.peers():
            self.rpc.cast(p, "sess", "delete_parked", client_id)
        return park["pending"]

    def _proto_park_remote(
        self, client_id: str, session_json: Dict, deadline: float
    ) -> bool:
        """Drain handoff phase 1 (sess v2): adopt a parked session from a
        draining peer. Routes go live HERE before the drainer drops its
        own, so an in-flight message lands in at least one bank."""
        self.park_session(client_id, session_json, deadline)
        return True

    def _proto_park_append(self, client_id: str, pendings) -> int:
        """Drain handoff phase 2: banked messages transferred AFTER the
        drainer's routes dropped (possible duplicates with phase-1 banking
        are QoS1 at-least-once, never loss)."""
        park = self._parked.get(client_id)
        if park is None:
            # the client resumed HERE between phase 1 and phase 2: its
            # session routes are live again — re-inject the backlog
            # through the normal publish path (dup-safe, never dropped)
            for m in pendings:
                self.publish(self._msg_from(m))
            return len(pendings)
        with self._park_lock:
            park["pending"].extend(pendings)
        return len(pendings)

    def _drain_one(self, peer: str, cid: str, rpc_call) -> bool:
        """Hand one parked session to `peer`; `rpc_call` performs the
        blocking calls (directly, or via an executor in app mode).

        Ordering: phase 1 makes the peer's park live (messages may now
        bank on BOTH sides — dups are at-least-once). Our routes then
        stay up while the bank drains in rounds, so a third node whose
        route table still lists us keeps landing messages in a bank that
        WILL be transferred; only once a sweep finds the bank empty do
        the local routes drop, and a final sweep ships any straggler
        that raced the drop. The residual window is a forward in flight
        after the final sweep — the same in-flight bound the resume
        marker protocol has (emqx_session_router.erl:171-220)."""
        park = self._parked.get(cid)
        if park is None:
            return False
        rpc_call(
            peer, "sess", "park_remote", cid, park["session"],
            park["deadline"],
        )
        while park["pending"]:
            with self._park_lock:
                batch, park["pending"] = park["pending"], []
            rpc_call(peer, "sess", "park_append", cid, batch)
        sid = f"parked:{cid}"
        for f in park["session"].get("subscriptions", {}):
            self.unsubscribe(sid, f)
        self._parked.pop(cid, None)
        if park["pending"]:  # raced the route drop: final sweep
            rpc_call(
                peer, "sess", "park_append", cid, list(park["pending"])
            )
        return True

    def drain_to(self, peer: str) -> int:
        """Rolling-upgrade drain (the relup analog, r3 verdict item 7;
        reference tooling: scripts/update_appup.escript — here the
        idiomatic equivalent is session handoff over the live protocol):
        every session parked on THIS node is re-parked on `peer` with the
        two-phase ordering above, then this node leaves the cluster.
        Returns the number of sessions handed off. The caller (node
        script / BrokerApp.drain) stops its listeners first so no new
        sessions appear mid-drain."""
        n = sum(
            self._drain_one(peer, cid, self.rpc.call)
            for cid in list(self._parked)
        )
        self.leave()
        return n

    async def drain_to_async(self, peer: str) -> int:
        """`drain_to` for app mode: the blocking rpc round-trips run in
        an executor so the event loop keeps serving inbound forwards —
        a message arriving mid-drain must still reach a bank (state
        mutations stay on the loop thread between the calls)."""
        import asyncio
        import functools

        loop = asyncio.get_running_loop()

        def rpc_sync(*a):
            return self.rpc.call(*a)

        n = 0
        for cid in list(self._parked):
            park = self._parked.get(cid)
            if park is None:
                continue
            await loop.run_in_executor(
                None,
                functools.partial(
                    rpc_sync, peer, "sess", "park_remote", cid,
                    park["session"], park["deadline"],
                ),
            )
            # drain the bank in rounds with routes still up (see
            # _drain_one's ordering comment), then drop + final sweep
            while park["pending"]:
                with self._park_lock:
                    batch, park["pending"] = park["pending"], []
                await loop.run_in_executor(
                    None,
                    functools.partial(
                        rpc_sync, peer, "sess", "park_append", cid, batch
                    ),
                )
            sid = f"parked:{cid}"
            for f in park["session"].get("subscriptions", {}):
                self.unsubscribe(sid, f)
            self._parked.pop(cid, None)
            if park["pending"]:
                await loop.run_in_executor(
                    None,
                    functools.partial(
                        rpc_sync, peer, "sess", "park_append", cid,
                        list(park["pending"]),
                    ),
                )
            n += 1
        await loop.run_in_executor(None, self.leave)
        return n

    # -- cluster config txn (emqx_cluster_rpc multicall parity) ------------
    def config_multicall(self, op: str, args: tuple) -> Dict[str, object]:
        """Append to the replicated config log and apply cluster-wide."""
        writer = min(self.membership.running_nodes())
        if writer == self.name:
            entry = self.conf_log.append(op, args)
        else:
            entry = tuple(self.rpc.call(writer, "conf", "append", op, args))
            self.conf_log.receive(entry)
        results: Dict[str, object] = {self.name: self.conf_log.apply_pending()}
        for p in self.membership.peers():
            try:
                results[p] = self.rpc.call(p, "conf", "receive_apply", entry)
            except RpcError as e:
                results[p] = ("badrpc", str(e))
        return results

    def _proto_conf_receive_apply(self, entry) -> int:
        self.conf_log.receive(tuple(entry))
        return self.conf_log.apply_pending()

    # -- introspection -----------------------------------------------------
    def stats(self) -> Dict[str, object]:
        s = dict(self.routes.stats())
        s["node"] = self.name
        s["peers"] = self.membership.peers()
        s["channels.count"] = len(self._channels)
        return s

    def flush(self, timeout: float = 10.0) -> None:
        """Drain async forwards/replication (test determinism)."""
        self.rpc.flush()
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lanes_lock:
                lanes = list(self._lanes.values())
            if not any(lane.running or lane.items for lane in lanes):
                return
            time.sleep(0.005)


def make_cluster(
    n: int,
    clock: Optional[Callable[[], float]] = None,
    forward_mode: str = "async",
) -> Tuple[LocalBus, List[ClusterNode]]:
    """n-node in-process cluster, fully joined."""
    bus = LocalBus()
    nodes = [
        ClusterNode(f"node{i}@cluster", bus, clock=clock, forward_mode=forward_mode)
        for i in range(n)
    ]
    for node in nodes[1:]:
        node.join(nodes[0].name)
    return bus, nodes
