"""Route table: exact-topic index + wildcard trie + TPU batch engine.

Parity with the reference's split storage (apps/emqx/src/emqx_router.erl:
111-125: plain topics go straight into the route table via dirty insert,
wildcard topics also enter the trie inside a transaction; match =
trie match + direct lookup, :128-141):

- exact (non-wildcard) filters: refcounted dict, O(1) lookup per topic;
- wildcard filters: the authoritative CPU trie (`TopicTrie`);
- BOTH feed the `RouteIndex` (shape-hash fast path + residual NFA,
  ops/route_index.py), so the TPU batch path resolves every filter kind in
  one kernel and the CPU path is only a correctness fallback/small-batch
  shortcut.

`match_batch` picks the TPU path when the batch is big enough to amortize a
dispatch (min_tpu_batch), mirroring how the reference splits work between
the caller process and the router worker pool (emqx_router.erl:188-189).
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Dict, List, Optional, Sequence

from emqx_tpu.broker.trie import TopicTrie
from emqx_tpu.ops import topics as T
from emqx_tpu.ops.route_index import RouteIndex

if TYPE_CHECKING:
    # ops/matcher.py imports jax at module level (its kernels are
    # jit-decorated), and this module rides the import chain of every
    # connection-worker process, which must stay off jax: one process
    # per chip. A Router is only ever BUILT in the process that owns it.
    from emqx_tpu.ops.matcher import MatcherConfig


class Router:
    def __init__(
        self,
        matcher_config: Optional[MatcherConfig] = None,
        min_tpu_batch: int = 64,
        enable_tpu: bool = True,
    ):
        self._exact: Dict[str, int] = {}
        self._trie = TopicTrie()
        self._index = RouteIndex()
        self._matcher = None  # lazy match-only DeviceRouter
        if matcher_config is None:
            from emqx_tpu.ops.matcher import MatcherConfig

            matcher_config = MatcherConfig()
        self._matcher_config = matcher_config
        self.min_tpu_batch = min_tpu_batch
        self.enable_tpu = enable_tpu
        # ('dp','tp') jax Mesh, set by the app alongside broker.mesh:
        # the lazy match-only engine then uploads its table mirrors
        # pre-sharded (replicated NamedSharding) like the serving engine
        self.mesh = None

    def __getstate__(self):
        # segment-state snapshots (ops/segments.SegmentStateSnapshot)
        # pickle the router; the lazy DeviceRouter holds device buffers
        # and is rebuilt on first use after restore. The mesh holds
        # live device objects (unpicklable by design) — the restoring
        # process re-attaches its OWN mesh (app boot wiring).
        d = self.__dict__.copy()
        d["_matcher"] = None
        d["mesh"] = None
        return d

    def __len__(self) -> int:
        return len(self._exact) + len(self._trie)

    def topics(self) -> List[str]:
        return list(self._exact) + list(self._trie.filters())

    def has_route(self, filter_: str) -> bool:
        return filter_ in self._exact or self._trie.has(filter_)

    def add_route(self, filter_: str) -> int:
        """Refcounted insert (one ref per subscriber entry). Returns the
        filter id so subscribe-storm callers skip a registry re-probe."""
        fid = self._index.add(filter_)
        if T.wildcard(filter_):
            self._trie.insert(filter_)
        else:
            self._exact[filter_] = self._exact.get(filter_, 0) + 1
        return fid

    def delete_route(self, filter_: str) -> None:
        self._index.remove(filter_)
        if T.wildcard(filter_):
            self._trie.delete(filter_)
        else:
            n = self._exact.get(filter_, 0) - 1
            if n > 0:
                self._exact[filter_] = n
            else:
                self._exact.pop(filter_, None)

    # -- matching ---------------------------------------------------------
    def match(self, topic: str) -> List[str]:
        """CPU single-topic match: direct lookup + trie walk."""
        out = []
        if topic in self._exact:
            out.append(topic)
        out.extend(self._trie.match(topic))
        return out

    def match_batch(self, topics: Sequence[str]) -> List[List[str]]:
        if not self.enable_tpu or len(topics) < self.min_tpu_batch:
            return [self.match(t) for t in topics]
        return self.matcher.match_batch(topics, fallback=self.match)

    def filter_id(self, filter_: str) -> Optional[int]:
        return self._index.filter_id(filter_)

    def filter_name(self, fid: int) -> Optional[str]:
        return self._index.filter_name(fid)

    @property
    def index(self) -> RouteIndex:
        return self._index

    @property
    def matcher(self):
        """Match-only device engine (its own table mirror; the broker's
        fan-out DeviceRouter keeps a separate one)."""
        if self._matcher is None:
            from emqx_tpu.models.router_model import DeviceRouter

            self._matcher = DeviceRouter(
                self._index, None, self._matcher_config, mesh=self.mesh
            )
        return self._matcher

    @property
    def matcher_config(self) -> MatcherConfig:
        return self._matcher_config
