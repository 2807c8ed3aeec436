"""Semantic routing tables: embedding-filter subscriptions on the
segment machinery + the similarity kernel fused into the serving step.

This is the plane that makes the TPU broker do something the Erlang
reference *cannot* (ROADMAP item 3; "Neural Router: Semantic Content
Matching for Agentic AI", PAPERS.md): route by payload MEANING. A
subscription may carry an embedding filter — a unit vector plus a
cosine-similarity threshold — and the serving step answers it with one
batched matmul riding the same launch, program, and compact readback
the topic fan-out already pays for:

  ``sims [B, E] = q_vecs [B, D]  @  sem_vec.T [D, E]``

followed by a threshold mask, an optional topic-scope (fid-membership)
mask, and a per-message top-k pick whose winner slots UNION into the
existing ``slots / slot_count / overflow`` compact contract BEFORE
readback (`union_semantic_slots`). Dispatch then treats semantic hits
as ordinary slot recipients — zero new host fan-out machinery.

`SemanticTable` is the fifth `DeviceSegmentManager` owner, in the
PR 9/11/13 idiom (docs/update_path.md):

- **packed segment** (written only by rebuilds/compaction):
  ``sem_vec [S, P, D]`` (f32 or bf16-quantized at upload) plus the
  int/float lanes ``sem_fid / sem_slot / sem_thresh [S, P]``;
- **hot segment** (append-only between compactions): the ``sem_hot_*``
  twins — an insert is D+3 op-logged scalar writes riding the next
  fused segment scatter, never an O(table) rebuild;
- **tombstone lane**: an unsubscribe writes ``sem_slot = -1`` (ONE
  op-logged write) — dead entries mask out of the kernel;
- **compaction** (`SemanticSegmentOwner` on the ONE `SegmentCompactor`):
  merges ``packed - tombstones + hot`` into a fresh exact-size table on
  the compact executor, pre-uploads it, and replays racing mutations
  from a journal — the ShapeIndex cycle verbatim;
- **placement** (`parallel.mesh.semantic_placement`): every array's
  leading axis is the shard-owner axis (entry owned by
  ``slot % shards``), sharded over 'tp' — the same slot-ownership
  regime as the CSR subscriber table, so per-shard semantic hits emit
  GLOBAL slot ids and concatenate over 'tp' with no lane rebase.

Scope semantics (``sem_fid``): ``fid >= 0`` binds the entry to a topic
filter — the entry only fires when that fid appears in the row's
matched set (topic AND similarity); ``fid == -1`` is an unscoped
filter — similarity alone routes it (any topic). Liveness is the slot
lane: ``sem_slot >= 0``.

The entry registry is a plain ``{slot: position}`` dict — deliberately
NOT the PR 9 open-addressing idiom: one entry exists per EXPLICIT
embedding filter (a per-subscription opt-in), orders of magnitude below
the 10M-row fan-out tables that forced the numpy registries.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from emqx_tpu.ops.contract import device_contract
from emqx_tpu.ops.nfa import _next_pow2

# registry position flag: entry lives in the hot segment
HOT_POS = 1 << 30

# device-snapshot array names (the segment-manager sync set)
SEM_KEYS = (
    "sem_vec", "sem_fid", "sem_slot", "sem_thresh",
    "sem_hot_vec", "sem_hot_fid", "sem_hot_slot", "sem_hot_thresh",
)


def normalize(vec, dim: int) -> np.ndarray:
    """Embedding intake: f32, exactly ``dim`` wide, unit-norm (cosine
    similarity is then one dot product). Zero vectors stay zero — they
    match nothing at any positive threshold."""
    v = np.asarray(vec, np.float32).reshape(-1)
    if v.shape[0] != dim:
        raise ValueError(
            f"embedding has dim {v.shape[0]}, table expects {dim}"
        )
    n = float(np.linalg.norm(v))
    if n > 1e-12:
        v = v / np.float32(n)
    return v.astype(np.float32)


# -- device kernel -----------------------------------------------------------


@device_contract(
    "semantic_match_step",
    # device-local by construction: the mesh builders psum the per-shard
    # qualifying counts OUTSIDE the kernel, exactly like the fan-out
    # compaction stages
    collectives=(),
    out_bounds={
        # semantic fan-out is bounded by the top-k pick BY DESIGN:
        # outputs scale with B * topk (and [B]), never with the entry
        # capacity E or the embedding dim D
        "sem_slots": lambda cfg: cfg["B"] * cfg["kslot"] * 4,
        "sem_count": lambda cfg: cfg["B"] * 4,
    },
)
def semantic_match_step(sem: Dict, q_vecs, matched, topk: int):
    """ONE batched similarity matmul + threshold/top-k mask.

    sem: the LOCAL shard's arrays ([1, ...] leading axis — inside
    shard_map each device sees its own 'tp' slice; single-device tables
    are shard 0 of 1). q_vecs: f32 [B, D] per-message embeddings.
    matched: int32 [B, K] sparse fids (-1 holes) from the topic match —
    the scope mask joins against it with the same scanned-membership
    overlay the CSR hot segment uses.

    Returns ``(sem_slots [B, topk], sem_count [B])``: the top-k
    qualifying entries' subscriber slots (score-ordered, -1 holes) and
    the UNCAPPED qualifying count (drives the `semantic.*` series and
    the truncation stat). Unlike Kslot overflow there is no dense
    fallback: top-k IS the delivery semantic ("route to the k most
    similar subscribers"), so truncation is a feature, not a degraded
    mode.
    """
    import jax
    import jax.numpy as jnp

    from emqx_tpu.ops.matcher import _member_mask

    if topk <= 0:
        raise ValueError("semantic matching requires topk > 0")
    vecs = jnp.concatenate(
        [sem["sem_vec"][0], sem["sem_hot_vec"][0]], axis=0
    )  # [E, D]
    fids = jnp.concatenate([sem["sem_fid"][0], sem["sem_hot_fid"][0]])
    slots = jnp.concatenate([sem["sem_slot"][0], sem["sem_hot_slot"][0]])
    ths = jnp.concatenate(
        [sem["sem_thresh"][0], sem["sem_hot_thresh"][0]]
    )
    E = vecs.shape[0]
    q = q_vecs
    if q.dtype != vecs.dtype:
        # bf16-quantized tables: the query casts down, the MXU
        # accumulates f32 (preferred_element_type pins it)
        q = q.astype(vecs.dtype)
    sims = jnp.matmul(
        q, vecs.T, preferred_element_type=jnp.float32
    )  # [B, E] f32
    live = slots >= 0
    scoped = fids >= 0
    # scope membership: entry fid in this row's matched set
    memb = _member_mask(matched, fids)  # [B, E]
    ok = (
        live[None, :]
        & (sims >= ths[None, :])
        & (~scoped[None, :] | memb)
    )
    count = jnp.sum(ok.astype(jnp.int32), axis=1)
    score = jnp.where(ok, sims, -jnp.inf)
    k = min(topk, E)
    top_v, top_i = jax.lax.top_k(score, k)
    sem_slots = jnp.where(
        top_v > -jnp.inf, slots[top_i], jnp.int32(-1)
    ).astype(jnp.int32)
    if k < topk:  # tiny tables: pad to the static contract width
        sem_slots = jnp.pad(
            sem_slots, ((0, 0), (0, topk - k)), constant_values=-1
        )
    return sem_slots, count


def union_semantic_slots(slots, sem_slots):
    """Union the semantic winners into the topic fan-out's compact slot
    rows BEFORE readback: ``[B, kslot] ++ [B, topk] -> [B, kslot+topk]``.

    Semantic entries already present in the topic part null out (a
    subscriber holding both a plain and a semantic subscription must
    not be delivered twice), and the TOPIC part is left byte-identical —
    `slot_count`/`overflow` keep their topic-only semantics, so the
    host's `slot_count > kslot` overflow derivation and the dense
    fallback contract are untouched. -1 holes are legal anywhere in a
    compact row (RouteResult contract), so no re-compaction is needed.
    """
    import jax.numpy as jnp

    dup = jnp.any(
        (sem_slots[:, :, None] == slots[:, None, :])
        & (sem_slots >= 0)[:, :, None],
        axis=2,
    )
    sem_clean = jnp.where(dup, jnp.int32(-1), sem_slots)
    return jnp.concatenate([slots, sem_clean], axis=1)


# -- host table --------------------------------------------------------------


class SemanticTable:
    """Host-side embedding-filter registry + its device mirror source
    (epoch/oplog/version protocol, docs/update_path.md).

    One entry per subscriber slot: ``slot`` is the broker's fan-out
    slot (`Broker._slot_subs`), so a semantic hit IS an ordinary slot
    recipient. ``fid`` scopes the entry to a topic filter (-1 =
    unscoped). Vectors normalize at intake.
    """

    HOT_MIN = 64  # minimum hot-segment capacity per shard (pow2)
    # hot population past this forces an inline rebuild instead of
    # another growth (the kernel concatenates hot into the matmul, so
    # hot size is a FLOP knob, not just memory)
    HOT_ABSORB_MAX = 1 << 14

    def __init__(self, dim: int = 64, topk: int = 16, shards: int = 1,
                 dtype: str = "float32"):
        if dim < 1:
            raise ValueError("semantic dim must be >= 1")
        if topk < 1:
            raise ValueError("semantic topk must be >= 1")
        if dtype not in ("float32", "bfloat16"):
            raise ValueError(f"semantic dtype {dtype!r}")
        self.dim = dim
        self.topk = topk
        self.dtype = dtype
        self.shards = S = max(1, int(shards))
        self._pcap = 64  # packed capacity PER SHARD
        self.sem_vec = np.zeros((S, self._pcap, dim), np.float32)
        self.sem_fid = np.full((S, self._pcap), -1, np.int32)
        self.sem_slot = np.full((S, self._pcap), -1, np.int32)
        self.sem_thresh = np.ones((S, self._pcap), np.float32)
        self._hcap = self.HOT_MIN
        self.sem_hot_vec = np.zeros((S, self._hcap, dim), np.float32)
        self.sem_hot_fid = np.full((S, self._hcap), -1, np.int32)
        self.sem_hot_slot = np.full((S, self._hcap), -1, np.int32)
        self.sem_hot_thresh = np.ones((S, self._hcap), np.float32)
        self._hot_tail = [0] * S
        self.live = 0
        self.packed_tombs = 0
        self.hot_tombs = 0
        # slot -> packed position | (HOT_POS | hot index), shard implied
        # by slot % shards (see module docstring for why a dict is fine)
        self._reg: Dict[int, int] = {}
        self.epoch = 0
        self.oplog: list = []
        self.version = 0
        self.OPLOG_MAX = 65536
        # compaction bookkeeping (the ShapeIndex/CsrTable cycle)
        self._structure_gen = 0
        self._journal: Optional[list] = None  # single-writer: loop

    # -- op-log plumbing ----------------------------------------------------
    def _bump(self) -> None:
        self.epoch += 1
        self.oplog.clear()
        self.version += 1

    def _log(self, name: str, flat_idx: int, val) -> None:
        # values stay python floats for the f32 lanes (the segment
        # scatter casts to the array dtype; int() here would truncate)
        self.version += 1
        if len(self.oplog) >= self.OPLOG_MAX:
            self._bump()
            return
        self.oplog.append((name, int(flat_idx), val))

    def _log_resync(self, name: str) -> None:
        from emqx_tpu.ops.segments import RESYNC

        self.version += 1
        if len(self.oplog) >= self.OPLOG_MAX:
            self._bump()
            return
        self.oplog.append((RESYNC, name, 0))

    # -- mutation -----------------------------------------------------------
    def add(self, slot: int, vec, threshold: float, fid: int = -1) -> bool:
        """Install (or replace) the embedding filter bound to a
        subscriber slot. Returns True when a NEW entry was created."""
        v = normalize(vec, self.dim)
        fid = -1 if fid is None or fid < 0 else int(fid)
        th = float(threshold)
        pos = self._reg.get(slot)
        if pos is not None:
            self._write_entry(slot, pos, v, th, fid)
            if self._journal is not None:
                self._journal.append(("add", slot, v, th, fid))
            return False
        s = slot % self.shards
        if self._hot_tail[s] >= self._hcap:
            if self.hot_fill >= self.HOT_ABSORB_MAX:
                # no compactor is draining hot: fold inline (epoch bump)
                self._rebuild([(slot, v, th, fid)])
                return True
            self._grow_hot()
        h = self._hot_tail[s]
        self._hot_tail[s] = h + 1
        self.sem_hot_vec[s, h] = v
        base = (s * self._hcap + h) * self.dim
        for d in range(self.dim):
            self._log("sem_hot_vec", base + d, float(v[d]))
        self.sem_hot_fid[s, h] = fid
        self._log("sem_hot_fid", s * self._hcap + h, fid)
        self.sem_hot_thresh[s, h] = th
        self._log("sem_hot_thresh", s * self._hcap + h, th)
        # slot lane LAST: liveness flips on only once the row is whole
        self.sem_hot_slot[s, h] = slot
        self._log("sem_hot_slot", s * self._hcap + h, slot)
        self._reg[slot] = h | HOT_POS
        self.live += 1
        if self._journal is not None:
            self._journal.append(("add", slot, v, th, fid))
        return True

    def _write_entry(self, slot: int, pos: int, v, th: float,
                     fid: int) -> None:
        """In-place filter replacement (same slot re-subscribes with a
        new embedding): scalar op-logged writes, no structural event."""
        s = slot % self.shards
        if pos & HOT_POS:
            h = pos & ~HOT_POS
            self.sem_hot_vec[s, h] = v
            base = (s * self._hcap + h) * self.dim
            for d in range(self.dim):
                self._log("sem_hot_vec", base + d, float(v[d]))
            self.sem_hot_fid[s, h] = fid
            self._log("sem_hot_fid", s * self._hcap + h, fid)
            self.sem_hot_thresh[s, h] = th
            self._log("sem_hot_thresh", s * self._hcap + h, th)
        else:
            self.sem_vec[s, pos] = v
            base = (s * self._pcap + pos) * self.dim
            for d in range(self.dim):
                self._log("sem_vec", base + d, float(v[d]))
            self.sem_fid[s, pos] = fid
            self._log("sem_fid", s * self._pcap + pos, fid)
            self.sem_thresh[s, pos] = th
            self._log("sem_thresh", s * self._pcap + pos, th)

    def remove(self, slot: int) -> bool:
        """Tombstone the entry bound to a slot: ONE op-logged write."""
        pos = self._reg.pop(slot, None)
        if pos is None:
            return False
        s = slot % self.shards
        if pos & HOT_POS:
            h = pos & ~HOT_POS
            self.sem_hot_slot[s, h] = -1
            self._log("sem_hot_slot", s * self._hcap + h, -1)
            self.hot_tombs += 1
        else:
            self.sem_slot[s, pos] = -1
            self._log("sem_slot", s * self._pcap + pos, -1)
            self.packed_tombs += 1
        self.live -= 1
        if self._journal is not None:
            self._journal.append(("remove", slot, None, 0.0, -1))
        return True

    def bulk_add(self, slots, vecs, thresholds, fids=None) -> None:
        """Vectorized cold load: one rebuild + one epoch bump."""
        slots = np.asarray(slots, np.int64)
        vecs = np.asarray(vecs, np.float32)
        ths = np.asarray(thresholds, np.float32)
        if fids is None:
            fids = np.full(len(slots), -1, np.int64)
        else:
            fids = np.asarray(fids, np.int64)
        n = np.linalg.norm(vecs, axis=1, keepdims=True)
        vecs = (vecs / np.maximum(n, 1e-12)).astype(np.float32)
        extra = [
            (int(slots[i]), vecs[i], float(ths[i]), int(fids[i]))
            for i in range(len(slots))
        ]
        self._rebuild(extra)

    def reshard(self, shards: int) -> None:
        """Re-partition over a new shard count (mesh attach after
        filters already landed). Epoch-bump rebuild."""
        shards = max(1, int(shards))
        if shards == self.shards:
            return
        # gather the live entries from the OLD layout before the shard
        # count (and every array's leading axis) changes
        ent = self._live_tuples()
        self.shards = shards
        self._structure_gen += 1
        self._journal = None
        built = self._build(ent, shards, self.dim)
        self._install(built)
        self._bump()

    # -- structure ----------------------------------------------------------
    def _grow_hot(self) -> None:
        nh = self._hcap * 2
        S = self.shards
        for name, fill in (
            ("sem_hot_fid", -1), ("sem_hot_slot", -1),
            ("sem_hot_thresh", 1.0),
        ):
            old = getattr(self, name)
            new = np.full((S, nh), fill, old.dtype)
            new[:, : self._hcap] = old  # append-only: indices preserved
            setattr(self, name, new)
            self._log_resync(name)
        old = self.sem_hot_vec
        new = np.zeros((S, nh, self.dim), np.float32)
        new[:, : self._hcap] = old
        self.sem_hot_vec = new
        self._log_resync("sem_hot_vec")
        self._hcap = nh

    @property
    def hot_fill(self) -> int:
        return sum(self._hot_tail) - self.hot_tombs

    @property
    def nbytes(self) -> int:
        """Device-table footprint: the eight mirrored arrays (bf16
        halves the vec arrays at upload; this reports the host f32)."""
        return sum(
            getattr(self, k).nbytes for k in SEM_KEYS
        )

    def __len__(self) -> int:
        return self.live

    def entries(self) -> List[Tuple[int, int, float]]:
        """(slot, fid, threshold) of every live entry (REST listing)."""
        out = []
        for slot, pos in self._reg.items():
            s = slot % self.shards
            if pos & HOT_POS:
                h = pos & ~HOT_POS
                out.append((
                    slot, int(self.sem_hot_fid[s, h]),
                    float(self.sem_hot_thresh[s, h]),
                ))
            else:
                out.append((
                    slot, int(self.sem_fid[s, pos]),
                    float(self.sem_thresh[s, pos]),
                ))
        return sorted(out)

    def live_arrays(self):
        """(vecs [E, D] f32, slots [E], fids [E], ths [E]) of every live
        entry — the host fallback / reference evaluator's view (loop
        thread; vectorized scans, no per-entry Python objects)."""
        vs, sl, fi, th = [], [], [], []
        for s in range(self.shards):
            m = self.sem_slot[s] >= 0
            if m.any():
                vs.append(self.sem_vec[s][m])
                sl.append(self.sem_slot[s][m])
                fi.append(self.sem_fid[s][m])
                th.append(self.sem_thresh[s][m])
            hm = self.sem_hot_slot[s] >= 0
            if hm.any():
                vs.append(self.sem_hot_vec[s][hm])
                sl.append(self.sem_hot_slot[s][hm])
                fi.append(self.sem_hot_fid[s][hm])
                th.append(self.sem_hot_thresh[s][hm])
        if not vs:
            z = np.empty(0, np.int32)
            return (np.empty((0, self.dim), np.float32), z, z,
                    np.empty(0, np.float32))
        return (
            np.concatenate(vs), np.concatenate(sl),
            np.concatenate(fi), np.concatenate(th),
        )

    def device_snapshot(self) -> Dict[str, np.ndarray]:
        out = {k: getattr(self, k) for k in SEM_KEYS}
        if self.dtype == "bfloat16":
            import ml_dtypes

            out = dict(out)
            for k in ("sem_vec", "sem_hot_vec"):
                out[k] = out[k].astype(ml_dtypes.bfloat16)
        return out

    def status(self) -> Dict:
        """Hotpath-REST / gauge block."""
        return {
            "filters": self.live,
            "dim": self.dim,
            "topk": self.topk,
            "dtype": self.dtype,
            "shards": self.shards,
            "packed_capacity": self._pcap * self.shards,
            "hot_fill": self.hot_fill,
            "tombstones": self.packed_tombs + self.hot_tombs,
            "bytes": self.nbytes,
        }

    # -- rebuild / compaction ----------------------------------------------
    def _live_tuples(self) -> List[Tuple[int, np.ndarray, float, int]]:
        vecs, slots, fids, ths = self.live_arrays()
        return [
            (int(slots[i]), vecs[i].copy(), float(ths[i]), int(fids[i]))
            for i in range(len(slots))
        ]

    def _rebuild(self, extra=()) -> None:
        ent = self._live_tuples()
        seen = {e[0] for e in extra}
        ent = [e for e in ent if e[0] not in seen] + list(extra)
        self._structure_gen += 1
        self._journal = None
        built = self._build(ent, self.shards, self.dim)
        self._install(built)
        self._bump()

    @staticmethod
    def _build(entries, shards: int, dim: int) -> Dict:
        """Pure-numpy exact-size packed build from (slot, vec, th, fid)
        tuples — safe on any thread (the compaction executor runs it)."""
        S = shards
        per: List[list] = [[] for _ in range(S)]
        for slot, v, th, fid in entries:
            per[slot % S].append((slot, v, th, fid))
        pcap = max(64, _next_pow2(max((len(p) for p in per), default=1)))
        vec = np.zeros((S, pcap, dim), np.float32)
        fidl = np.full((S, pcap), -1, np.int32)
        slotl = np.full((S, pcap), -1, np.int32)
        thl = np.ones((S, pcap), np.float32)
        reg: Dict[int, int] = {}
        n = 0
        for s in range(S):
            for i, (slot, v, th, fid) in enumerate(sorted(per[s])):
                vec[s, i] = v
                fidl[s, i] = fid
                slotl[s, i] = slot
                thl[s, i] = th
                reg[slot] = i
                n += 1
        return {
            "pcap": pcap, "sem_vec": vec, "sem_fid": fidl,
            "sem_slot": slotl, "sem_thresh": thl, "reg": reg, "n": n,
        }

    # oplog-covered-by: every caller bumps the epoch after install
    def _install(self, built: Dict) -> None:
        S = self.shards
        self._pcap = built["pcap"]
        self.sem_vec = built["sem_vec"]
        self.sem_fid = built["sem_fid"]
        self.sem_slot = built["sem_slot"]
        self.sem_thresh = built["sem_thresh"]
        self._hcap = self.HOT_MIN
        self.sem_hot_vec = np.zeros((S, self._hcap, self.dim), np.float32)
        self.sem_hot_fid = np.full((S, self._hcap), -1, np.int32)
        self.sem_hot_slot = np.full((S, self._hcap), -1, np.int32)
        self.sem_hot_thresh = np.ones((S, self._hcap), np.float32)
        self._hot_tail = [0] * S
        self.hot_tombs = 0
        self.packed_tombs = 0
        self.live = built["n"]
        self._reg = dict(built["reg"])

    def begin_compact(self) -> Dict:
        cap = {
            "entries": self._live_tuples(),
            "shards": self.shards,
            "dim": self.dim,
            "gen": self._structure_gen,
        }
        self._journal = []
        return cap

    @staticmethod
    def build_compact(cap: Dict) -> Dict:
        built = SemanticTable._build(
            cap["entries"], cap["shards"], cap["dim"]
        )
        built["gen"] = cap["gen"]
        return built

    def apply_compact(self, built: Dict) -> bool:
        """Install a built table (loop thread) + replay the journal of
        mutations that raced the build. False = capture invalidated by
        a structural rebuild (the cycle aborts cleanly)."""
        if self._journal is None or built["gen"] != self._structure_gen:
            self._journal = None
            return False
        journal, self._journal = self._journal, None
        self._structure_gen += 1
        self._install(built)
        self._bump()
        for op, slot, v, th, fid in journal:
            if op == "add":
                self.add(slot, v, th, fid)
            else:
                self.remove(slot)
        return True


class SemanticSegmentOwner:
    """Compaction adapter for a `SemanticTable` + its segment manager:
    merge ``packed - tombstones + hot`` into a fresh exact-size table
    off the subscribe path, pre-uploading the packed arrays on the
    compact executor (`SegmentCompactor` drives the cycle)."""

    key = "semantic"

    def __init__(self, semtab: SemanticTable, manager, placement=None,
                 hot_entries: int = 1024, tombstone_frac: float = 0.25):
        self.semtab = semtab
        self.manager = manager
        self._placement = placement
        self.hot_entries = hot_entries
        self.tombstone_frac = tombstone_frac

    def needs_compact(self) -> bool:
        t = self.semtab
        if t.hot_fill >= self.hot_entries:
            return True
        tombs = t.packed_tombs + t.hot_tombs
        return tombs > 0 and tombs >= self.tombstone_frac * max(1, t.live)

    def begin(self):
        return self.semtab.begin_compact()

    def build(self, cap):
        built = SemanticTable.build_compact(cap)
        # pre-upload the packed arrays on THIS (executor) thread: the
        # built table is immutable, so the device_put is race-free
        import jax

        dtype = self.semtab.dtype
        dev = {}
        for name in ("sem_vec", "sem_fid", "sem_slot", "sem_thresh"):
            arr = built[name]
            if name == "sem_vec" and dtype == "bfloat16":
                import ml_dtypes

                arr = arr.astype(ml_dtypes.bfloat16)
            if self._placement is not None:
                dev[name] = self._placement(name, arr)
            else:
                dev[name] = jax.device_put(arr)
        built["dev"] = dev
        return built

    def apply(self, built):
        merged = self.semtab.hot_fill
        if not self.semtab.apply_compact(built):
            return None
        return self.semtab.epoch, built["dev"], 0, merged
