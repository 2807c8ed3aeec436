"""Segmented device tables: ONE manager under every table owner.

Before this module each device-table owner (the route/shape indexes, the
NFA residual engine, the subscriber/group bitmaps, the retained-topic
chunks) carried its own upload path, its own epoch bookkeeping, and its
own readback-site hygiene — three slightly different copies of the same
delta-overlay machinery (ROADMAP item 3). `DeviceSegmentManager` is that
machinery written once:

- **full uploads** on the source's `epoch` changing (structural events:
  growth, rehash, salt bump), with the `free_retired` one-epoch grace
  for in-flight executor batches still holding the previous snapshot;
- **O(delta) updates**: the op-log suffix since the last sync replays as
  ONE fused device launch (`segment_scatter_insert`, a registered
  `@device_contract` kernel) covering every touched array — not one
  dispatch per array, which multiplies the fixed per-launch cost into
  the subscribe-visibility window;
- **per-array resync markers**: a source that rebuilt ONE small array
  (the shape index growing its hot segment, the retained index appending
  a chunk) logs `("!resync", name, 0)` and only that array re-uploads —
  the multi-GB packed tables never ride along;
- **offered buffers**: background compaction (`SegmentCompactor`) builds
  the merged packed table on an executor thread, `jax.device_put`s it
  there, and `offer()`s the device buffer tagged with the post-apply
  epoch — the next serving `prepare()` adopts it instead of paying the
  full upload on the critical path;
- **snapshot/restore**: the host tables a manager mirrors are plain
  numpy + dicts; `SegmentStateSnapshot` checkpoints them through
  `DurableState` so a rolling upgrade restores million-entry tables
  without replaying every subscribe.

Op-log protocol (sources: NfaBuilder, ShapeIndex, SubscriberTable,
GroupTable, DeviceRetainedIndex): `epoch` int, `version` int (total
mutation counter), `oplog` list of `(array_name, flat_index, value)`
scalar writes in program order — plus the `("!resync", array_name, 0)`
marker — and `device_snapshot() -> {name: np.ndarray}`. An epoch bump
clears the log (consumers that far behind resync fully).

Replay soundness of the `!resync` marker: the re-upload reads the LIVE
host array, which reflects every write up to the sync point, i.e. a
superset of every logged write in the suffix — so suffix writes for a
resync'd array are dropped, and writes logged after the marker are
already in the uploaded bytes.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from emqx_tpu.ops.contract import device_contract

RESYNC = "!resync"  # op-log marker: (RESYNC, array_name, 0)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@device_contract(
    "segment_scatter_insert",
    # host->device delta replay is device-local by construction: on a
    # mesh the placed sharding propagates through the scatter, no
    # collective may appear
    collectives=(),
)
def segment_scatter_impl(flats: Dict, idxs: Dict, vals: Dict) -> Dict:
    """The O(delta) update kernel: `flats[k][idxs[k]] = vals[k]` for every
    touched array, in ONE jitted program. Padded index vectors repeat one
    write (idempotent), so the program is keyed on pow2 delta buckets,
    not exact delta lengths. Outputs are fresh buffers — the inputs are
    deliberately NOT donated: in-flight executor batches may still hold
    the previous mirror generation (the same grace contract free_retired
    encodes for full uploads)."""
    return {k: flats[k].at[idxs[k]].set(vals[k]) for k in flats}


_scatter_jit = None


def _segment_scatter(flats, idxs, vals):
    global _scatter_jit
    if _scatter_jit is None:
        import jax

        _scatter_jit = jax.jit(segment_scatter_impl)
    return _scatter_jit(flats, idxs, vals)


class DeviceSegmentManager:
    """Device-resident mirror of one incrementally-mutated host source.

    `sync(src)` returns `{name: device_array}` matching
    `src.device_snapshot()`. All internal state is mutated under `_lock`
    (the retained flush path syncs from the dispatch executor while the
    loop thread inserts); callers receive a fresh shallow-copied dict, so
    a snapshot held across a later sync never tears.
    """

    def __init__(
        self,
        placement=None,
        free_retired: bool = False,
        name: str = "",
        metrics=None,
    ) -> None:
        """`placement`: optional fn(name, np_or_dev_array) -> device array
        applied to full uploads AND re-pinned after delta scatters — e.g.
        a NamedSharding device_put for SPMD serving, so churn stays
        O(delta) scatters on a mesh too (per-shard hot segments ride the
        same replicated placement as the packed tables).

        `free_retired`: explicitly `.delete()` the device buffers a full
        re-upload replaces, with ONE epoch of grace (the generation
        retired by rebuild N is freed at rebuild N+1) — in-flight
        executor batches still holding the previous snapshot stay valid.
        """
        self.name = name
        # per-kernel attribution sink (observe/profiler.py); None keeps
        # the manager usable as a bare library object
        self.metrics = metrics
        self._lock = threading.Lock()
        self._arrays: Optional[Dict] = None  # guarded-by: _lock
        self._epoch = -1  # guarded-by: _lock
        self._pos = 0  # guarded-by: _lock
        self._torn = False  # guarded-by: _lock
        self._placement = placement
        self._free_retired = free_retired
        self._retired: Optional[list] = None  # guarded-by: _lock
        self._offer: Optional[Tuple] = None  # guarded-by: _lock
        # observability counters, read by DeviceRouter.segment_status()
        self.full_resyncs = 0  # guarded-by: _lock
        self.delta_launches = 0  # guarded-by: _lock
        self.array_resyncs = 0  # guarded-by: _lock

    # -- background-compaction handoff ------------------------------------
    def offer(self, epoch: int, arrays: Dict, pos: int = 0) -> None:
        """Pre-built device buffers for the NEXT full resync, tagged with
        the source epoch they represent at op-log position `pos`. Adopted
        only when the epochs still match at sync time (a later structural
        event invalidates the offer); the op-log suffix past `pos`
        replays on top as usual."""
        with self._lock:
            self._offer = (epoch, dict(arrays), pos)

    def has_mirror(self) -> bool:
        with self._lock:
            return self._arrays is not None

    def device_bytes(self) -> Dict[int, int]:
        """Bytes of the mirror resident on each device id — where the
        placement hook REALLY put the shards, read off the arrays."""
        with self._lock:
            arrays = list((self._arrays or {}).values())
        out: Dict[int, int] = {}
        for arr in arrays:
            for sh in arr.addressable_shards:
                out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
        return out

    # -- fused-launch rider handoff ---------------------------------------
    def peek_delta(self, src):
        """Rider support (broker/session_store.py): the current mirror +
        the op-log suffix as per-array last-write-wins vectors, WITHOUT
        applying anything — the caller fuses the scatter into a serving
        launch (`session_ack_step` riding `session_route_step`) and
        hands the produced device arrays back via `adopt`. Returns
        ``(arrays, per_name_writes, pos, epoch)``, or None when the
        mirror needs a full resync / the suffix carries resync markers —
        those (rare, structural) paths go through `sync()` instead."""
        with self._lock:
            if (
                self._arrays is None
                or self._epoch != src.epoch
                or self._torn
            ):
                return None
            ops = src.oplog[self._pos :]
            per: Dict[str, Dict[int, int]] = {}
            for name, idx, val in ops:
                if name == RESYNC or name not in self._arrays:
                    return None
                per.setdefault(name, {})[idx] = val
            return dict(self._arrays), per, len(src.oplog), self._epoch

    def adopt(self, arrays: Dict, pos: int, epoch: int) -> bool:
        """Install rider-produced device arrays as the mirror at op-log
        position ``pos``. Refused (False) when a structural event moved
        the mirror past the rider's epoch/position — the host arrays are
        authoritative, so the refused rider's writes are already covered
        by the full re-upload that superseded it."""
        with self._lock:
            if (
                self._arrays is None
                or self._epoch != epoch
                or self._torn
                or pos < self._pos
            ):
                return False
            self._arrays = dict(arrays)
            self._pos = pos
            return True

    # -- sync --------------------------------------------------------------
    def sync(self, src) -> Dict:
        with self._lock:
            v0 = getattr(src, "version", None)
            out = self._sync_locked(src)
            if v0 is not None and getattr(src, "version", None) != v0:
                # torn read: an off-thread sync raced the mutator. The
                # snapshot is a usable superset for THIS call (consumers
                # re-verify matches on host), but it must never be
                # cached as clean — the next sync re-uploads.
                self._torn = True
            return out

    def _sync_locked(self, src) -> Dict:  # holds-lock: _lock
        if self._arrays is None or self._epoch != src.epoch or self._torn:
            self._torn = False
            return self._full_resync(src)
        return self._delta_sync(src)

    def _put(self, name: str, arr):
        if self._placement is not None:
            return self._placement(name, arr)
        import jax.numpy as jnp

        return jnp.asarray(arr)

    def _full_resync(self, src) -> Dict:  # holds-lock: _lock
        if self._free_retired:
            old = self._retired
            self._retired = (
                list(self._arrays.values()) if self._arrays else None
            )
            for arr in old or ():
                try:
                    arr.delete()
                except Exception:  # noqa: BLE001 — free is advisory
                    pass
        offer = self._offer
        self._offer = None
        if offer is not None and offer[0] != src.epoch:
            offer = None  # stale: a later structural event superseded it
        offered = offer[1] if offer is not None else {}
        self._arrays = {}
        for k, v in src.device_snapshot().items():
            if k in offered:
                self._arrays[k] = offered[k]
            else:
                self._arrays[k] = self._put(k, v.copy())
        self._epoch = src.epoch
        self.full_resyncs += 1
        if offer is not None:
            # adopted buffers represent op-log position `pos`; the
            # suffix (e.g. compaction-journal replay) scatters on top
            self._pos = offer[2]
            return self._delta_sync(src)
        self._pos = len(src.oplog)
        return dict(self._arrays)

    def _delta_sync(self, src) -> Dict:  # holds-lock: _lock
        import jax.numpy as jnp

        ops = src.oplog[self._pos :]
        snap = None
        if not ops:
            return dict(self._arrays)
        resync_names = {a for name, a, _v in ops if name == RESYNC}
        per: Dict[str, Dict[int, int]] = {}
        for name, idx, val in ops:
            if name == RESYNC or name in resync_names:
                continue  # the live re-upload supersedes these writes
            per.setdefault(name, {})[idx] = val  # last write per slot wins
        if resync_names:
            snap = src.device_snapshot()
            for name in resync_names:
                if name in snap:
                    self._arrays[name] = self._put(name, snap[name].copy())
                else:
                    self._arrays.pop(name, None)
                self.array_resyncs += 1
        # arrays that appeared without a marker (defensive: a source
        # growing its snapshot dict) upload too
        for name in list(per):
            if name not in self._arrays:
                if snap is None:
                    snap = src.device_snapshot()
                self._arrays[name] = self._put(name, snap[name].copy())
                self.array_resyncs += 1
                del per[name]
        if per:
            flats, idxs, vals, shapes = {}, {}, {}, {}
            for name, writes in per.items():
                arr = self._arrays[name]
                shapes[name] = arr.shape
                flats[name] = arr.reshape(-1)
                ix = np.fromiter(
                    writes.keys(), dtype=np.int32, count=len(writes)
                )
                vv = np.array(list(writes.values()), dtype=arr.dtype)
                # pad to a pow2 bucket (repeating one write is a no-op)
                # so the fused program recompiles per (touched-array-set,
                # size-bucket) combination, not per delta length
                n = len(ix)
                npad = max(16, _next_pow2(n))
                if npad != n:
                    ix = np.pad(ix, (0, npad - n), mode="edge")
                    vv = np.pad(vv, (0, npad - n), mode="edge")
                idxs[name] = jnp.asarray(ix)
                vals[name] = jnp.asarray(vv)
            # every touched array updates in ONE device launch
            out = _segment_scatter(flats, idxs, vals)
            self.delta_launches += 1
            for name in flats:
                new = out[name].reshape(shapes[name])
                if self._placement is not None:
                    # the scatter's jit may drop the placed sharding;
                    # re-pin (device-side reshard — no host re-upload)
                    new = self._placement(name, new)
                self._arrays[name] = new
        self._pos = len(src.oplog)
        # shallow copy: callers may hold the snapshot across a later sync
        return dict(self._arrays)


# -- background compaction ---------------------------------------------------

_compact_pool = None
_compact_pool_lock = threading.Lock()


def compact_pool():
    """Process-wide single-worker executor for segment compaction builds.
    One worker: compaction is a throughput background chore, and two
    concurrent multi-GB table builds would double peak host memory."""
    global _compact_pool
    with _compact_pool_lock:
        if _compact_pool is None:
            from concurrent.futures import ThreadPoolExecutor

            _compact_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="segment-compact"
            )
        return _compact_pool


class SegmentCompactor:
    """Housekeeping-driven merge of hot segments into the packed tables.

    The loop thread owns every host table; the `segment-compact` executor
    thread only ever touches the immutable capture/built artifacts and
    `jax.device_put` (thread-safe). Per owner, one cycle is:

      loop:    cap   = owner.begin()          (array memcpys + journal on)
      thread:  built = owner.build(cap)       (pure numpy merge)
      thread:  bufs  = device_put(built)      (upload OFF the serving path)
      loop:    epoch = owner.apply(built)     (swap + journal replay)
      loop:    owner.manager.offer(epoch, bufs)

    so the next serving `prepare()` adopts the pre-uploaded buffers and
    the subscribe path never pays an O(table) rebuild or upload.
    """

    def __init__(self, metrics=None, interval_s: float = 5.0):
        self.metrics = metrics
        self.interval_s = interval_s
        self._busy = False  # single-writer: loop
        self._last: Dict[str, float] = {}  # single-writer: loop
        self._need_since: Dict[str, float] = {}  # single-writer: loop
        self.runs = 0  # single-writer: loop
        self.aborted = 0  # single-writer: loop

    def lag_s(self, key: str, now: Optional[float] = None) -> float:
        t0 = self._need_since.get(key)
        if t0 is None:
            return 0.0
        return (time.monotonic() if now is None else now) - t0

    def tick(self, owners) -> bool:
        """One housekeeping tick (loop thread): update gauges, and start
        at most one background compaction cycle. Returns True when a
        cycle was started."""
        import asyncio

        now = time.monotonic()
        started = False
        for owner in owners:
            key = owner.key
            need = owner.needs_compact()
            if need and key not in self._need_since:
                self._need_since[key] = now
            elif not need:
                self._need_since.pop(key, None)
            if self.metrics is not None and key == "shapes":
                self.metrics.gauge_set(
                    "router.compact.lag.seconds", self.lag_s(key, now)
                )
            if started or self._busy or not need:
                continue
            if now - self._last.get(key, 0.0) < self.interval_s:
                continue
            self._busy = True
            started = True
            asyncio.ensure_future(self._run(owner))
        return started

    async def _run(self, owner) -> None:
        import asyncio

        t0 = time.perf_counter()
        key = owner.key
        try:
            cap = owner.begin()
            loop = asyncio.get_running_loop()
            built = await loop.run_in_executor(
                compact_pool(), owner.build, cap
            )
            # back on the loop: swap host arrays + replay the journal,
            # then hand the pre-uploaded device buffers to the manager
            applied = owner.apply(built)
            if applied is None:
                self.aborted += 1
                if self.metrics is not None:
                    self.metrics.inc("router.compact.aborted")
            else:
                epoch, bufs, pos, merged = applied
                owner.manager.offer(epoch, bufs, pos)
                self.runs += 1
                if self.metrics is not None:
                    self.metrics.inc("router.compact.runs")
                    self.metrics.inc("router.compact.merged", merged)
                    if getattr(owner, "_placement", None) is not None:
                        # the rebuilt table pre-uploaded straight into
                        # the sharded mesh layout — no host gather, no
                        # serving-path re-placement (docs/scale_out.md)
                        self.metrics.inc("mesh.shard.compact.runs")
        except Exception:  # noqa: BLE001 — one bad cycle must not stop
            self.aborted += 1
            if self.metrics is not None:
                self.metrics.inc("router.compact.aborted")
            import logging

            logging.getLogger("emqx_tpu.segments").exception(
                "segment compaction cycle failed (%s)", key
            )
        finally:
            self._busy = False
            self._last[key] = time.monotonic()
            self._need_since.pop(key, None)
            if self.metrics is not None:
                self.metrics.observe(
                    "router.compact.seconds", time.perf_counter() - t0
                )

    def compact_now(self, owner) -> bool:
        """Synchronous cycle (tests): begin+build+apply+offer on
        the calling thread. Returns False when the cycle aborted."""
        cap = owner.begin()
        built = owner.build(cap)
        applied = owner.apply(built)
        if applied is None:
            self.aborted += 1
            return False
        epoch, bufs, pos, merged = applied
        owner.manager.offer(epoch, bufs, pos)
        self.runs += 1
        if self.metrics is not None:
            self.metrics.inc("router.compact.runs")
            self.metrics.inc("router.compact.merged", merged)
            if getattr(owner, "_placement", None) is not None:
                self.metrics.inc("mesh.shard.compact.runs")
        return True


class ShapeSegmentOwner:
    """Compaction adapter for a `ShapeIndex` + its manager: merges the
    hot segment into the packed table and purges tombstones."""

    key = "shapes"

    def __init__(self, shapes, manager, placement=None,
                 hot_entries: int = 1024, tombstone_frac: float = 0.25):
        self.shapes = shapes
        self.manager = manager
        self._placement = placement
        self.hot_entries = hot_entries
        self.tombstone_frac = tombstone_frac

    def needs_compact(self) -> bool:
        s = self.shapes
        if s.hot_live >= self.hot_entries:
            return True
        return s.packed_tombstones > 0 and (
            s.packed_tombstones >= self.tombstone_frac * s._Tcap
        )

    def begin(self):
        return self.shapes.begin_compact()

    def build(self, cap):
        built = type(self.shapes).build_compact(cap)
        # upload on THIS (executor) thread: the built table is immutable,
        # so the device_put is race-free and the serving path never pays it
        arr = built["tab"].reshape(-1)
        if self._placement is not None:
            built["dev"] = self._placement("shape_tab", arr)
        else:
            import jax

            built["dev"] = jax.device_put(arr)
        return built

    def apply(self, built):
        merged = self.shapes.hot_live
        epoch = self.shapes.apply_compact(built)
        if epoch is None:
            return None
        return epoch, {"shape_tab": built["dev"]}, 0, merged


class BitmapGrowthOwner:
    """Compaction adapter for the subscriber bitmap matrix: PROACTIVE
    growth. `SubscriberTable` growth is an epoch bump (full re-upload of
    the biggest array in the process); growing at 3/4 occupancy from
    housekeeping — and pre-uploading the grown matrix off-thread — keeps
    the bump off the subscribe path entirely."""

    key = "bitmaps"

    def __init__(self, subtab, index, manager, placement=None,
                 headroom: float = 0.75):
        self.subtab = subtab
        self.index = index
        self.manager = manager
        self._placement = placement
        self.headroom = headroom

    def needs_compact(self) -> bool:
        if getattr(self.subtab, "sparse", False):
            return False  # the CSR representation has its own owner
        return (
            self.index.num_filters_capacity
            > self.headroom * self.subtab._fcap
        )

    def begin(self):
        # grow NOW on the loop (one memcpy; the expensive half — the
        # device upload — happens on the executor below), then capture
        # a consistent copy + the op-log position it represents
        tab = self.subtab
        tab.pack(_next_pow2(int(tab._fcap * 2)))
        return {
            "epoch": tab.epoch,
            "pos": len(tab.oplog),
            "arr": tab.arr.copy(),
        }

    def build(self, cap):
        if self._placement is not None:
            cap["dev"] = self._placement("sub_bitmaps", cap["arr"])
        else:
            import jax

            cap["dev"] = jax.device_put(cap["arr"])
        return cap

    def apply(self, built):
        if self.subtab.epoch != built["epoch"]:
            return None  # another structural event superseded the copy
        return built["epoch"], {"sub_bitmaps": built["dev"]}, built["pos"], 0


# -- durable snapshot/restore ------------------------------------------------


class SegmentStateSnapshot:
    """Rolling-upgrade story for the segment tables: pickle the host
    sources (numpy arrays + registries — mnesia disc_copies analog) to a
    sidecar file; `DurableState` carries the pointer + generation in its
    kv so a replacement process restores million-entry tables instead of
    replaying every subscribe.

    `capture()` must run on the thread that owns the tables (the loop).
    """

    def __init__(self, path: str, capture: Callable[[], Dict],
                 install: Optional[Callable[[Dict], None]] = None):
        self.path = path
        self._capture = capture
        self._install = install

    def save(self) -> Dict:
        import os
        import pickle

        state = self._capture()
        tmp = f"{self.path}.{os.getpid()}.tmp"
        with open(tmp, "wb") as f:
            pickle.dump(state, f, protocol=pickle.HIGHEST_PROTOCOL)
        os.replace(tmp, self.path)
        return {
            "path": self.path,
            "at": time.time(),
            "keys": sorted(state),
        }

    def load(self, meta: Optional[Dict]) -> Optional[Dict]:
        import os
        import pickle

        path = (meta or {}).get("path", self.path)
        if not path or not os.path.exists(path):
            return None
        with open(path, "rb") as f:
            state = pickle.load(f)
        if self._install is not None:
            self._install(state)
        return state
