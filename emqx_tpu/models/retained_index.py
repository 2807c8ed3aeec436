"""Device index for retained-message replay storms.

BASELINE config 5 is a retained-replay storm: a wildcard SUBSCRIBE against
millions of retained messages. The reference walks its retained-topic
table per subscribe (emqx_retainer_mnesia.erl:146-152 match_messages) —
O(store) per subscriber.

TPU-native inversion of the routing kernel: the stored retained TOPICS
are the batch, and the incoming subscribe FILTER becomes a one-entry
shape-index table. One `shape_route_step` launch per chunk of stored
topics answers "which retained topics match this filter" as a dense
match matrix — the same kernel that routes publishes, pointed the other
way. Topics are pre-tokenized into pinned device chunks at insert time,
so a replay query is pure kernel launches + one small readback per chunk.

Matches are re-verified on host (`T.match`) before use — kernel caps and
hash collisions can only cost a false candidate, never a wrong replay.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional

import numpy as np

from emqx_tpu.ops import topics as T


class StormJob(NamedTuple):
    """A prepared replay storm, ready to ride a serving-path launch.

    Built on the event-loop thread (`DeviceRetainedIndex.prepare_storm`)
    so the table build and chunk uploads never race host mutation; the
    tuple is immutable device state safe to hand to an executor thread
    (the same contract as `DeviceRouter.prepare`). `decode` turns the
    per-chunk match matrices (host numpy) back into {filter: row-index
    array} — device-free, so it runs wherever the readback landed.
    """

    index: "DeviceRetainedIndex"
    filters: List[str]
    fids: Dict[int, str]
    shape_tables: Dict
    nfa_tables: Optional[Dict]
    kwargs: Dict
    chunks: List[object]  # device chunk buffers (uploaded)
    nrows: int  # live-row high-water at prepare time

    def decode(self, matched_list) -> Dict[str, np.ndarray]:
        return self.index._decode_storm(
            self.fids, self.filters, matched_list, self.nrows
        )


def _retained_step(
    shape_tables, nfa_tables, bm, *, m_active, with_nfa, salt, max_levels,
    narrow,
):
    """Storm launch: lengths derived on-device (topics cannot contain
    NUL — emqx_topic validate rejects it — so length = count of nonzero
    bytes), which removes the lengths operand from every launch; the
    result is narrowed to int16 when fids fit. Every byte crossing the
    host<->device link per launch is paid per storm, so operands are
    kept minimal."""
    import jax.numpy as jnp

    from emqx_tpu.models.router_model import shape_route_step_impl

    ln = jnp.sum((bm != 0).astype(jnp.int32), axis=1)
    out = shape_route_step_impl(
        shape_tables,
        nfa_tables,
        None,
        bm,
        ln,
        m_active=m_active,
        with_nfa=with_nfa,
        salt=salt,
        max_levels=max_levels,
    )
    m = out["matched"]
    return m.astype(jnp.int16) if narrow else m


_retained_step_jit = None


def _get_retained_step():
    global _retained_step_jit
    if _retained_step_jit is None:
        import jax
        from functools import partial

        _retained_step_jit = partial(
            jax.jit,
            static_argnames=(
                "m_active", "with_nfa", "salt", "max_levels", "narrow"
            ),
        )(_retained_step)
    return _retained_step_jit


# Topics per device launch. Sized large: per-launch dispatch overhead
# (host->device descriptor round-trips) dominates the kernel's per-row
# cost, so fewer, bigger launches win. One chunk = 64MB of topic bytes +
# 4MB lengths in HBM.
CHUNK = 1 << 20


class DeviceRetainedIndex:
    # retained churn is row-granular (up to `bucket` logged bytes per
    # insert/delete), so the op-log cap sits higher than the index
    # sources' — a full chunk re-upload is 64MB on the link
    OPLOG_MAX = 1 << 18

    def __init__(self, max_bytes: int = 64, max_levels: int = 8,
                 mesh=None):
        """`mesh`: a ('dp','tp') jax Mesh — chunk mirrors then upload
        through the segment manager pre-sharded (rows over 'dp', the
        layout `dist_fused_step` scans), and storm filter tables place
        replicated like every other match table. None = single-device
        placement, unchanged."""
        self.max_bytes = max_bytes  # hard cap (device-budget gate)
        self.max_levels = max_levels
        self.mesh = mesh
        # actual storage width: a pow2 bucket grown to the longest stored
        # topic. Every storm moves chunk bytes across the host<->device
        # link at least once, so padding to the cap when topics are short
        # doubles or quadruples the transfer for nothing.
        self.bucket = min(16, max_bytes)
        self._rows: Dict[str, int] = {}  # topic -> global row
        self._by_row: List[Optional[str]] = []
        self._free: List[int] = []
        self._tombstones = 0  # live rows removed (match_many fast path)
        # host chunks, mirrored on device by the ONE segment-table
        # manager (ops/segments.py): retained add/remove reaches the
        # device as row scatters (delta-overlay protocol), a fresh chunk
        # re-uploads alone (resync marker), and only a bucket-width
        # change pays a full re-upload (epoch bump). The manager's lock +
        # torn-version guard covers storm uploads running on executor
        # threads while the loop thread inserts.
        # device_snapshot builds the chunk_N names dynamically, so the
        # OL checker discovers the backing store from this annotation:
        self._host_b: List[np.ndarray] = []  # mirrored-array
        from emqx_tpu.ops.segments import DeviceSegmentManager

        if mesh is not None:
            from emqx_tpu.parallel.mesh import (
                retained_placement,
                table_placement,
            )

            self._seg = DeviceSegmentManager(
                placement=retained_placement(mesh), name="retained"
            )
            self._table_place = table_placement(mesh)
        else:
            self._seg = DeviceSegmentManager(name="retained")
            self._table_place = None
        self.epoch = 0
        self.oplog: list = []
        self.version = 0

    # -- delta protocol -----------------------------------------------------
    def device_snapshot(self) -> Dict[str, np.ndarray]:
        return {f"chunk_{c}": b for c, b in enumerate(self._host_b)}

    def _bump_epoch(self) -> None:
        self.epoch += 1
        self.oplog.clear()
        self.version += 1

    def _log_resync(self, name: str) -> None:
        self.version += 1
        if len(self.oplog) >= self.OPLOG_MAX:
            self._bump_epoch()
            return
        from emqx_tpu.ops.segments import RESYNC

        self.oplog.append((RESYNC, name, 0))

    def _log_row(self, c: int, i: int) -> None:
        """Op-log one row's bytes (post-write): the delta scatter replays
        the whole `bucket`-wide row, trailing zeros included, so the
        on-device length derivation stays exact."""
        self.version += 1
        if len(self.oplog) >= self.OPLOG_MAX:
            self._bump_epoch()
            return
        row = self._host_b[c][i]
        base = i * self.bucket
        name = f"chunk_{c}"
        for b in range(self.bucket):
            self.oplog.append((name, base + b, int(row[b])))

    def _grow_bucket(self, need: int) -> None:
        from emqx_tpu.ops.nfa import _next_pow2

        nb = min(max(self.bucket, _next_pow2(need)), self.max_bytes)
        if nb == self.bucket:
            return
        for c in range(len(self._host_b)):
            new = np.zeros((CHUNK, nb), np.uint8)
            new[:, : self.bucket] = self._host_b[c]
            self._host_b[c] = new
        self.bucket = nb
        self._bump_epoch()  # every chunk changed geometry: full upload

    def __len__(self) -> int:
        return len(self._rows)

    # -- mutation ----------------------------------------------------------
    def add(self, topic: str) -> bool:
        """False when the topic doesn't fit the device budget (too long /
        too deep) — the caller's CPU path remains authoritative for it."""
        if topic in self._rows:
            return True
        enc = topic.encode()
        if len(enc) > self.max_bytes or len(T.words(topic)) > self.max_levels:
            return False
        if len(enc) > self.bucket:
            self._grow_bucket(len(enc))
        if self._free:
            row = self._free.pop()
            self._by_row[row] = topic
            self._tombstones -= 1
        else:
            row = len(self._by_row)
            self._by_row.append(topic)
            if row >= len(self._host_b) * CHUNK:
                self._host_b.append(
                    np.zeros((CHUNK, self.bucket), np.uint8)
                )
                # a fresh chunk re-uploads ALONE; existing chunks'
                # mirrors are untouched
                self._log_resync(f"chunk_{len(self._host_b) - 1}")
        self._rows[topic] = row
        c, i = divmod(row, CHUNK)
        self._host_b[c][i, : len(enc)] = np.frombuffer(enc, np.uint8)
        self._host_b[c][i, len(enc):] = 0
        self._log_row(c, i)
        return True

    def bulk_add(self, topics: List[str]) -> int:
        """Vectorized initial load (restore); returns count added.
        Topics must fit the device budget (raises otherwise — callers
        pre-filter, the same contract `add` enforces per topic)."""
        from emqx_tpu.ops.tokenizer import encode_topics

        fresh = [t for t in topics if t not in self._rows]
        longest = 0
        for t in fresh:
            if len(T.words(t)) > self.max_levels:
                raise ValueError(f"bulk_add: topic too deep: {t!r}")
            longest = max(longest, len(t.encode()))
        if longest > self.bucket:
            self._grow_bucket(longest)
        pos = 0
        while pos < len(fresh):
            # fill the tail of the current chunk
            row0 = len(self._by_row)
            c, i0 = divmod(row0, CHUNK)
            if c >= len(self._host_b):
                self._host_b.append(np.zeros((CHUNK, self.bucket), np.uint8))
            take = min(CHUNK - i0, len(fresh) - pos)
            batch = fresh[pos : pos + take]
            mat, _lens, too_long = encode_topics(batch, self.bucket)
            if too_long.any():
                raise ValueError("bulk_add: topic exceeds max_bytes")
            self._host_b[c][i0 : i0 + take] = mat
            # slab write: re-upload the touched chunk wholesale instead
            # of logging CHUNK x bucket scalar deltas
            self._log_resync(f"chunk_{c}")
            for k, t in enumerate(batch):
                self._rows[t] = row0 + k
            self._by_row.extend(batch)
            pos += take
        return len(fresh)

    def remove(self, topic: str) -> None:
        row = self._rows.pop(topic, None)
        if row is None:
            return
        self._by_row[row] = None
        self._free.append(row)
        self._tombstones += 1
        c, i = divmod(row, CHUNK)
        self._host_b[c][i, :] = 0  # len derives 0 -> zero words
        self._log_row(c, i)

    # -- query ------------------------------------------------------------
    def _build_tables(self, filters: List[str], floor: int = 0):
        """-> (idx, fid->filter, launch kwargs) for a storm's filter set."""
        import jax

        from emqx_tpu.ops.route_index import RouteIndex

        idx = RouteIndex()
        fids: Dict[int, str] = {}
        for f in filters:
            if len(T.words(f)) > self.max_levels:
                raise ValueError(f"filter too deep for device budget: {f}")
            fids[idx.add(f)] = f
        # storm tables are one-shot (a fresh table per storm, never
        # delta-synced); in mesh mode they place through the canonical
        # replicated layout so the fused sharded program reads them
        # without a per-launch reshard
        put = self._table_place or (lambda _n, a: jax.device_put(a))
        shape_tables = {
            k: put(k, v.copy())
            for k, v in idx.shapes.device_snapshot().items()
        }
        with_nfa = idx.residual_count > 0
        nfa_tables = (
            {
                k: put(k, v.copy())
                for k, v in idx.nfa.device_snapshot().items()
            }
            if with_nfa
            else None
        )
        kwargs = dict(
            m_active=idx.shapes.m_active(floor=floor) if floor else
            idx.shapes.m_active(),
            with_nfa=with_nfa,
            salt=idx.salt,
            max_levels=self.max_levels,
            narrow=idx.num_filters_capacity < (1 << 15) - 1,
        )
        return idx, fids, shape_tables, nfa_tables, kwargs

    def _ensure_chunks(self) -> list:
        """Sync the chunk mirrors through the segment manager; returns
        the device buffer list in chunk order. Safe off the mutating
        thread: the manager serializes concurrent syncs and never caches
        a torn upload as clean (version guard) — a torn snapshot is
        still used for THIS storm (a superset of the pre-mutation rows;
        decode re-verifies against live state)."""
        segs = self._seg.sync(self)
        return [segs[f"chunk_{c}"] for c in range(len(self._host_b))]

    def _launch_all(self, shape_tables, nfa_tables, kwargs) -> list:
        """Dispatch one storm launch per chunk (lengths derived
        on-device; no lengths operand), all before any readback."""
        step = _get_retained_step()
        return [
            step(shape_tables, nfa_tables, d, **kwargs)
            for d in self._ensure_chunks()
        ]

    def prepare_storm(self, filters: List[str]) -> Optional[StormJob]:
        """Build one replay storm's filter tables + chunk buffers so the
        serving pipeline can fuse the match into its next route launch
        (`DeviceRouter.route_prepared(..., retained=job)`): the storm
        then costs ZERO extra launches and zero extra readbacks for
        single-chunk stores, instead of its own launch+readback train.

        Returns None when the index is empty or any filter exceeds the
        device budget (callers fall back to the authoritative CPU walk).
        Must run on the thread that mutates the index (the event loop) —
        the same contract as `DeviceRouter.prepare`.
        """
        if not self._host_b:
            return None
        if any(len(T.words(f)) > self.max_levels for f in filters):
            return None
        _idx, fids, shape_tables, nfa_tables, kwargs = self._build_tables(
            filters, floor=1
        )
        return StormJob(
            index=self,
            filters=list(filters),
            fids=fids,
            shape_tables=shape_tables,
            nfa_tables=nfa_tables,
            kwargs=kwargs,
            chunks=self._ensure_chunks(),
            nrows=len(self._by_row),
        )

    def match(self, filter_: str) -> Optional[List[str]]:  # readback-site
        """Retained topics matching `filter_`, or None when the filter
        itself exceeds the device budget (caller falls back to CPU)."""
        if len(T.words(filter_)) > self.max_levels:
            return None
        _idx, _fids, shape_tables, nfa_tables, kwargs = self._build_tables(
            [filter_]
        )
        outs = self._launch_all(shape_tables, nfa_tables, kwargs)
        nrows = len(self._by_row)
        out: List[str] = []
        for c, matched in enumerate(outs):
            hit_rows = np.nonzero((np.asarray(matched) >= 0).any(axis=1))[0]
            base = c * CHUNK
            for i in hit_rows:
                row = base + int(i)
                # padding rows (len 0) can match plen-0 filters like '#'
                t = self._by_row[row] if row < nrows else None
                # host verification: false candidates cost a check, false
                # replay would cost correctness
                if t is not None and T.match(t, filter_):
                    out.append(t)
        return out

    def warm(self, filters: List[str]) -> None:  # readback-site
        """Upload chunks + compile the storm program WITHOUT reading
        results back (`match_many` works unwarmed, it just pays the XLA
        compile inline; the program is keyed on the filter table's size
        bucket, so warm with a representative filter set)."""
        import jax

        _idx, _f, shape_tables, nfa_tables, kwargs = self._build_tables(
            filters, floor=1
        )
        jax.block_until_ready(
            self._launch_all(shape_tables, nfa_tables, kwargs)
        )

    def match_many(  # readback-site
        self, filters: List[str]
    ) -> Dict[str, np.ndarray]:
        """Answer a replay STORM: many wildcard subscribes in one pass.

        All filters enter ONE shape table; each chunk launch matches every
        stored topic against every filter simultaneously, and the [B, M]
        result (one fid lane per filter shape — within a shape at most one
        filter matches a topic, so the lanes are exact) scatters rows to
        subscribers. Per-storm cost is the same handful of kernel launches
        a single filter pays — the storm amortizes to ~O(1) passes, vs the
        reference's O(store) walk PER subscriber.

        Returns {filter: row-index array}; materialize topics lazily with
        `topic_at`. Unlike `match`, hits are spot-checked (sampled), not
        exhaustively re-verified — the 2^-64 combined-hash collision class
        is accepted here, matching the module's differential test gate.
        """
        if not self._host_b:  # empty index: nothing can match
            return {f: np.empty(0, np.int64) for f in filters}
        _idx, fids, shape_tables, nfa_tables, kwargs = self._build_tables(
            filters, floor=1
        )
        outs = self._launch_all(shape_tables, nfa_tables, kwargs)
        # all chunks dispatched before any readback (launches pipeline);
        # read back per chunk rather than as one giant buffer
        matched_list = [np.asarray(m) for m in outs]
        del outs
        return self._decode_storm(
            fids, filters, matched_list, len(self._by_row)
        )

    def _decode_storm(
        self, fids, filters: List[str], matched_list, nrows: int
    ) -> Dict[str, np.ndarray]:
        """Host-side storm decode: per-chunk match matrices (numpy) ->
        {filter: row-index array}. Device-free, so the fused serving path
        (`StormJob.decode`) runs it on whatever thread did the readback."""
        lanes = int(matched_list[0].shape[1])
        flat = np.concatenate([np.asarray(m).ravel() for m in matched_list])
        # flat index = (row_g * lanes + lane); group hit rows by fid with
        # one stable argsort instead of per-chunk unique passes. Dtypes
        # stay narrow: the sort is the host-side hot spot at 5M+ pairs.
        nhits = int(np.count_nonzero(flat >= 0))
        if nhits == flat.size and lanes == 1 and nrows == flat.size:
            # dense storm (every stored row matched): skip the index
            # materialization entirely
            hits = rows_g = np.arange(flat.size, dtype=np.int64)
        else:
            hits = np.nonzero(flat >= 0)[0]
            rows_g = hits if lanes == 1 else hits // lanes
            oob = rows_g >= nrows  # padding rows can match plen-0 filters
            if oob.any():
                keep = ~oob
                hits, rows_g = hits[keep], rows_g[keep]
        if self._tombstones:
            # tombstoned rows (removed topics) can still match plen-0
            # filters like '#' via their zeroed length. Slice to nrows:
            # on the fused path the store may have grown since prepare.
            live = np.zeros(nrows, dtype=bool)
            for r, t in enumerate(self._by_row[:nrows]):
                live[r] = t is not None
            keep = live[rows_g]
            hits, rows_g = hits[keep], rows_g[keep]
        hit_fids = flat[hits]
        order = np.argsort(hit_fids, kind="stable")
        rows_g = rows_g[order]
        hit_fids = hit_fids[order]
        bounds = np.nonzero(np.diff(hit_fids))[0] + 1
        starts = np.concatenate([[0], bounds])
        ends = np.concatenate([bounds, [len(hit_fids)]])
        out: Dict[str, np.ndarray] = {f: np.empty(0, np.int64) for f in filters}
        rng = np.random.default_rng(0)
        for s, e in zip(starts, ends):
            if e <= s:
                continue
            f = fids.get(int(hit_fids[s]))
            if f is None:
                continue
            sel = rows_g[s:e]
            out[f] = sel
            # sampled verification (see docstring)
            row = int(rng.choice(sel))
            t = self._by_row[row]
            assert t is None or T.match(t, f), (t, f)
        return out

    def topic_at(self, row: int) -> Optional[str]:
        return self._by_row[row] if 0 <= row < len(self._by_row) else None
