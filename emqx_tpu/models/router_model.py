"""The flagship routing pipeline: topics -> matched filters -> subscriber bitmaps.

This fuses, in one jitted program, what the reference does per message across
three modules (SURVEY.md §3.3 hot path):

  emqx_router:match_routes  (emqx_router.erl:128-141)  -> NFA batch match
  emqx_broker:subscribers    (emqx_broker.erl:505-530) -> bitmap gather
  dispatch fan-out OR-union                            -> segment OR-reduce

Subscriber state is a dense bitmap matrix ``sub_bitmaps [Fcap, W]`` (uint32):
row = filter id, bit = local subscriber slot. The fanout output for a topic is
the OR over its matched filters' rows — one gather + reduce, MXU-adjacent
VPU work that scales with W, and the axis the multi-chip layout shards
("tensor parallelism" over subscriber lanes; see emqx_tpu.parallel).

Per-batch stats (routed topics, total matches, fanout bits) are computed
on-device so multi-chip deployments can psum them over the mesh instead of
funneling counters through a host (reference analog: emqx_metrics counter
arrays, emqx_metrics.erl:439).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from emqx_tpu.observe import faults as _faults
from emqx_tpu.observe import profiler as _prof
from emqx_tpu.ops.contract import device_contract
from emqx_tpu.ops.csr_table import CsrSegmentOwner, CsrTable, sparse_fanout_slots
from emqx_tpu.ops.nfa import _next_pow2
from emqx_tpu.ops.semantic_table import (
    SemanticSegmentOwner,
    semantic_match_step,
    union_semantic_slots,
)


def popcount32(x):
    """Vectorized popcount for uint32 (no TPU popcnt primitive needed)."""
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return (x * jnp.uint32(0x01010101)) >> 24


def fanout_bitmaps(sub_bitmaps, matched):
    """OR the bitmap rows of each topic's matched filters.

    sub_bitmaps: uint32 [Fcap, W]; matched: int32 [B, K]; -> uint32 [B, W].
    """
    safe = jnp.maximum(matched, 0)  # [B, K]
    rows = sub_bitmaps[safe]  # [B, K, W]
    valid = (matched >= 0)[:, :, None]
    rows = jnp.where(valid, rows, jnp.uint32(0))
    # OR-reduce over K (no lax.reduce_or over axis for uint32? use bitwise.reduce)
    return jax.lax.reduce(
        rows, jnp.uint32(0), jax.lax.bitwise_or, dimensions=(1,)
    )


@device_contract(
    "compact_fanout_slots",
    # the whole point of the stage: outputs scale with B*kslot, never
    # with the bitmap width W
    out_bounds={
        "slots": lambda cfg: cfg["B"] * cfg["kslot"] * 4,
        "count": lambda cfg: cfg["B"] * 4,
        "overflow": lambda cfg: cfg["B"],
    },
)
def compact_fanout_slots(bitmaps, kslot: int):
    """On-device sparse fan-out compaction: set bits -> slot-id lists.

    Makes the device->host readback O(matches) instead of O(B x W):
    instead of shipping the dense ``[B, W]`` uint32 bitmap matrix, ship
    ``slots [B, kslot]`` int32 (ascending slot ids, -1 padded),
    ``count [B]`` (UNCAPPED total set bits), and ``overflow [B]`` (count
    > kslot: the row's dense bitmap must be fetched instead, so
    correctness never depends on the cap).

    Two stages keep peak memory O(B * kslot * 32), not O(B * W * 32)
    (W grows with the connection table; expanding every word's 32 bits
    first would materialize the whole slot universe per row):

      1. left-pack the NONZERO words (index + value) with the same
         iota + prefix-sum + capped scatter as the matched-fid
         compaction (`ops.matcher._compact`). A nonzero word carries
         >= 1 set bit, so > kslot nonzero words implies count > kslot —
         word-stage drops only ever happen on rows already flagged
         overflow;
      2. expand only the packed words into their 32 candidate slots and
         left-pack those into the final [B, kslot] buffer.
    """
    from emqx_tpu.ops.matcher import _compact

    # a stable name on the device side (the trace's op metadata)
    with jax.named_scope("fanout_compact"):
        B, W = bitmaps.shape
        kw = min(kslot, W)  # a row cannot have more nonzero words than W
        nz = bitmaps != 0
        pos = jnp.cumsum(nz.astype(jnp.int32), axis=1) - 1
        idx = jnp.where(nz & (pos < kw), pos, kw)
        rows = jnp.arange(B, dtype=jnp.int32)[:, None]
        widx = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32), (B, W))
        pwidx = jnp.full((B, kw), -1, jnp.int32).at[rows, idx].set(
            widx, mode="drop"
        )
        pword = jnp.zeros((B, kw), jnp.uint32).at[rows, idx].set(
            bitmaps, mode="drop"
        )
        # unpacked holes have pword == 0, so every candidate they produce
        # is already -1 — no extra validity mask needed
        bit = (
            pword[:, :, None] >> jnp.arange(32, dtype=jnp.uint32)
        ) & jnp.uint32(1)
        cand = jnp.where(
            bit.astype(bool),
            pwidx[:, :, None] * 32 + jnp.arange(32, dtype=jnp.int32),
            jnp.int32(-1),
        ).reshape(B, kw * 32)
        slots, _ = _compact(cand, kslot)
        count = jnp.sum(popcount32(bitmaps).astype(jnp.int32), axis=1)
        return slots, count, count > kslot


# `batch_match_syms`' causes, in the order the step's `nfa_flagged[1:4]` and
# the `route.nfa.flagged.<cause>` counters carry them
NFA_FLAG_CAUSES = ("too_deep", "frontier_overflow", "match_overflow")
# its walk's two scalars, in the order `nfa_flagged[4:]` carries them, and
# the counters the host adds them to
NFA_WALK_COUNTERS = (
    ("levels", "route.nfa.levels"),
    ("frontier_peak", "route.nfa.frontier_peak"),
)


def shape_route_step_impl(
    shape_tables,
    nfa_tables,
    sub_bitmaps,
    bytes_mat,
    lengths,
    group_tables=None,
    client_hash=None,
    topic_hash=None,
    rand=None,
    sem_tables=None,
    q_vecs=None,
    rule_feats=None,
    rule_valid=None,
    *,
    m_active: int,
    with_nfa: bool,
    salt: int,
    max_levels: int = 16,
    frontier: int = 32,
    max_matches: int = 64,
    probes: int = 8,
    shape_probes: Optional[int] = None,
    with_groups: bool = False,
    share_strategy: int = 0,
    dp_axis: Optional[str] = None,
    kslot: int = 0,
    kg: int = 0,
    sem_topk: int = 0,
    rule_progs: tuple = (),
):
    """The serving-path kernel: shape index + (residual NFA) + fanout.

    Tokenizes once, matches via the O(#shapes) hash path
    (ops/shape_index.shape_match_device), runs the general NFA walk only
    when residual filters exist (`with_nfa`), ORs subscriber bitmaps over
    every matched fid. `matched` is SPARSE ([B, M(+K)] with -1 holes), not
    prefix-compacted.

    ``kslot > 0`` adds the sparse fan-out compaction stage
    (`compact_fanout_slots`): the output dict grows slots [B, kslot] /
    slot_count [B] / overflow [B], so the host can read back O(matches)
    compact slot lists and fetch dense bitmap rows only for the
    (rare, overflow-flagged) rows whose fan-out exceeds the cap.

    ``sub_bitmaps`` may instead be a CSR table dict (ops/csr_table.py):
    the fan-out stage then runs `sparse_fanout_slots` over the
    O(subscriptions) slot lists and emits the same compact contract
    directly (no dense bitmaps exist; overflow rows rebuild on host).
    ``kg`` is the CSR gather-window bound (0 = 2 * kslot).

    ``sem_tables`` set (ops/semantic_table.py array dict) engages the
    SEMANTIC routing plane: `semantic_match_step` runs one batched
    similarity matmul over ``q_vecs`` [B, D] in the SAME program, and
    its top-``sem_topk`` winner slots union into the compact slot rows
    before readback (`union_semantic_slots` — the topic part stays
    byte-identical, so slot_count/overflow keep topic-only semantics).
    Requires the compact stage (kslot > 0). The qualifying count rides
    the readback as ``sem_count`` [B].

    ``rule_progs`` (a static tuple of compiled WHERE programs,
    rules/compile.py) evaluates every compiled rule over the
    ``rule_feats``/``rule_valid`` [B, F] feature batch inside this
    launch; the bool masks ride readback as ``rule_masks`` [R, B].
    Defaults leave the trace bit-identical (golden jaxprs unchanged).
    """
    import jax.numpy as jnp

    from emqx_tpu.ops import tokenizer as tok
    from emqx_tpu.ops.matcher import batch_match_syms
    from emqx_tpu.ops.shape_index import SHAPE_PROBES, shape_match_device

    if shape_probes is None:
        # must cover the host placement bound (ShapeIndex._place probes
        # SHAPE_PROBES slots) or cluster-tail entries become invisible
        shape_probes = SHAPE_PROBES
    # stable device-side stage names (`jax.named_scope`: op metadata in
    # a trace, no primitive added): match (nfa_walk inside it),
    # csr_gather, fanout_compact, share_pick
    with jax.named_scope("match"):
        h1, h2, nwords, dollar = tok.tokenize_device(
            bytes_mat, lengths, salt, max_levels
        )
        matched = shape_match_device(
            shape_tables, m_active, h1, h2, nwords, dollar, probes=shape_probes
        )
        flags = nwords > max_levels
        nfa_flagged = None
        if with_nfa:
            with jax.named_scope("nfa_walk"):
                syms = tok.vocab_lookup_device(nfa_tables, h1, h2, probes)
                m2, _c2, f2, causes2, walk = batch_match_syms(
                    nfa_tables,
                    syms,
                    nwords,
                    dollar,
                    frontier=frontier,
                    max_matches=max_matches,
                    probes=probes,
                )
            matched = jnp.concatenate([matched, m2], axis=1)
            flags = flags | f2
            # rows the residual engine flagged, then by cause
            # (NFA_FLAG_CAUSES; a row may have several): four ints a
            # launch for the `route.nfa.flagged` counters, over the live
            # rows alone (a bucket's padding rows are empty topics), as
            # the host counts `route.nfa.matches`; then the walk's two
            # scalars (NFA_WALK_COUNTERS)
            live = lengths > 0
            nfa_flagged = jnp.stack([
                jnp.sum((c & live).astype(jnp.int32))
                for c in (f2, *(causes2[k] for k in NFA_FLAG_CAUSES))
            ] + [walk[k] for k, _ in NFA_WALK_COUNTERS])
        mcount = jnp.sum((matched >= 0).astype(jnp.int32), axis=1)
    sparse_out = None
    if isinstance(sub_bitmaps, dict):  # CSR representation
        bitmaps = None
        s_slots, s_count, s_ovf, s_live = sparse_fanout_slots(
            sub_bitmaps, matched, kslot=kslot, kg=kg
        )
        sparse_out = (s_slots, s_count, s_ovf)
        fanout_bits = jnp.sum(s_live)
    elif sub_bitmaps is not None:
        bitmaps = fanout_bitmaps(sub_bitmaps, matched)
        fanout_bits = jnp.sum(popcount32(bitmaps).astype(jnp.int32))
    else:  # match-only callers (Router.match_batch) skip the fan-out half
        bitmaps = None
        fanout_bits = jnp.int32(0)
    if with_groups and group_tables is not None:
        with jax.named_scope("share_pick"):
            pick_gid, pick_idx = share_pick_device(
                group_tables,
                matched,
                client_hash,
                topic_hash,
                rand,
                strategy=share_strategy,
                dp_axis=dp_axis,
            )
    else:
        pick_gid = pick_idx = None
    stats = {
        "routed": jnp.sum((mcount > 0).astype(jnp.int32)),
        "matches": jnp.sum(mcount),
        "fanout_bits": fanout_bits,
    }
    out = {
        "matched": matched,
        "mcount": mcount,
        "flags": flags,
        "bitmaps": bitmaps,
        "pick_gid": pick_gid,
        "pick_idx": pick_idx,
        "stats": stats,
    }
    if nfa_flagged is not None:
        out["nfa_flagged"] = nfa_flagged
    if sparse_out is not None:
        out["slots"], out["slot_count"], out["overflow"] = sparse_out
    elif kslot > 0 and bitmaps is not None:
        slots, scount, sovf = compact_fanout_slots(bitmaps, kslot)
        out["slots"] = slots
        out["slot_count"] = scount
        out["overflow"] = sovf
    if sem_tables is not None:
        if "slots" not in out:
            raise ValueError(
                "semantic routing requires the compact fan-out stage "
                "(kslot > 0 and a subscriber table)"
            )
        sem_slots, sem_count = semantic_match_step(
            sem_tables, q_vecs, matched, sem_topk
        )
        out["slots"] = union_semantic_slots(out["slots"], sem_slots)
        out["sem_count"] = sem_count
    if rule_progs:
        from emqx_tpu.rules.compile import eval_rule_masks

        out["rule_masks"] = eval_rule_masks(
            rule_progs, rule_feats, rule_valid
        )
    return out


shape_route_step = device_contract(
    "shape_route_step",
    collectives=(),
    out_bounds={
        "slots": lambda cfg: cfg["B"] * cfg["kslot"] * 4,
        "slot_count": lambda cfg: cfg["B"] * 4,
    },
)(partial(
    jax.jit,
    static_argnames=(
        "m_active",
        "with_nfa",
        "salt",
        "max_levels",
        "frontier",
        "max_matches",
        "probes",
        "shape_probes",
        "with_groups",
        "share_strategy",
        "dp_axis",
        "kslot",
        "kg",
        "sem_topk",
        "rule_progs",
    ),
)(shape_route_step_impl))

# Serving-path entry with input-buffer donation: the per-batch lengths
# buffer is donated so XLA reuses it for a matching output (mcount /
# slot_count are the same int32 [B] shape) instead of allocating fresh —
# steady-state batches recycle their upload buffers. The token-bytes
# matrix is NOT donated: uint8 [B, max_bytes] aliases no output aval,
# so XLA would ignore the donation and warn on every compile. Same
# trace as `shape_route_step` (donation is a compile option, not a
# program change), so no second device contract. Only PER-BATCH
# operands may donate — tables/bitmaps persist across batches.
shape_route_step_donated = partial(
    jax.jit,
    static_argnames=(
        "m_active",
        "with_nfa",
        "salt",
        "max_levels",
        "frontier",
        "max_matches",
        "probes",
        "shape_probes",
        "with_groups",
        "share_strategy",
        "dp_axis",
        "kslot",
        "kg",
        "sem_topk",
        "rule_progs",
    ),
    donate_argnames=("lengths",),
)(shape_route_step_impl)

# Second registry entry for the SAME serving jit traced with a CSR
# subscriber table instead of the dense bitmap matrix: the sparse mode
# compiles a different program (gather-union fan-out, no [B, W]
# bitmaps), so it gets its own golden jaxpr + byte bounds. The audit
# harness (tools/analysis/device_contract.py) builds the CSR workload.
sparse_shape_route_step = device_contract(
    "sparse_shape_route_step",
    collectives=(),
    out_bounds={
        "slots": lambda cfg: cfg["B"] * cfg["kslot"] * 4,
        "slot_count": lambda cfg: cfg["B"] * 4,
    },
)(shape_route_step)


def session_route_step_impl(
    shape_tables,
    nfa_tables,
    sub_bitmaps,
    bytes_mat,
    lengths,
    sess_tables,
    sess_idxs,
    sess_vals,
    sess_clock,
    group_tables=None,
    client_hash=None,
    topic_hash=None,
    rand=None,
    sem_tables=None,
    q_vecs=None,
    rule_feats=None,
    rule_valid=None,
    *,
    m_active: int,
    with_nfa: bool,
    salt: int,
    max_levels: int = 16,
    frontier: int = 32,
    max_matches: int = 64,
    probes: int = 8,
    shape_probes: Optional[int] = None,
    with_groups: bool = False,
    share_strategy: int = 0,
    kslot: int = 0,
    kg: int = 0,
    sem_topk: int = 0,
    rule_progs: tuple = (),
    sweep_k: int = 0,
):
    """Publish routing + the session-ack stage as ONE device program.

    The composition of two audited kernels (`shape_route_step` +
    `session_ack_step`, docs/sessions.md): a batch's pending inflight
    writes — delivery inserts, PUBACK/PUBREC/PUBCOMP/PUBREL clears —
    scatter onto the device session table inside the SAME launch the
    batch pays for routing, and (``sweep_k > 0``) the QoS retransmit /
    session-expiry sweep's compact row lists ride the same coalesced
    readback. The updated session arrays stay on device (the store
    adopts them as the new mirror); only the O(sweep_k) sweep outputs
    ever cross the link — no extra launch, no extra transfer.
    """
    from emqx_tpu.ops.session_table import session_ack_impl

    out = shape_route_step_impl(
        shape_tables,
        nfa_tables,
        sub_bitmaps,
        bytes_mat,
        lengths,
        group_tables,
        client_hash,
        topic_hash,
        rand,
        sem_tables,
        q_vecs,
        rule_feats,
        rule_valid,
        m_active=m_active,
        with_nfa=with_nfa,
        salt=salt,
        max_levels=max_levels,
        frontier=frontier,
        max_matches=max_matches,
        probes=probes,
        shape_probes=shape_probes,
        with_groups=with_groups,
        share_strategy=share_strategy,
        kslot=kslot,
        kg=kg,
        sem_topk=sem_topk,
        rule_progs=rule_progs,
    )
    out["session"] = session_ack_impl(
        sess_tables, sess_idxs, sess_vals, sess_clock, sweep_k=sweep_k
    )
    return out


# jit entry for the session-fused program. Not a separate device
# contract: it composes two registered kernels (`shape_route_step` +
# `session_ack_step`), each audited with its own golden jaxpr — the
# same rationale as shape_route_step_donated's shared contract.
session_route_step = partial(
    jax.jit,
    static_argnames=(
        "m_active",
        "with_nfa",
        "salt",
        "max_levels",
        "frontier",
        "max_matches",
        "probes",
        "shape_probes",
        "with_groups",
        "share_strategy",
        "kslot",
        "kg",
        "sem_topk",
        "rule_progs",
        "sweep_k",
    ),
)(session_route_step_impl)


def fused_route_retained_step_impl(
    shape_tables,
    nfa_tables,
    sub_bitmaps,
    bytes_mat,
    lengths,
    ret_shape_tables,
    ret_nfa_tables,
    ret_bytes,
    group_tables=None,
    client_hash=None,
    topic_hash=None,
    rand=None,
    sem_tables=None,
    q_vecs=None,
    rule_feats=None,
    rule_valid=None,
    *,
    m_active: int,
    with_nfa: bool,
    salt: int,
    ret_m_active: int,
    ret_with_nfa: bool,
    ret_salt: int,
    ret_max_levels: int,
    ret_narrow: bool,
    max_levels: int = 16,
    frontier: int = 32,
    max_matches: int = 64,
    probes: int = 8,
    shape_probes: Optional[int] = None,
    with_groups: bool = False,
    share_strategy: int = 0,
    kslot: int = 0,
    kg: int = 0,
    sem_topk: int = 0,
    rule_progs: tuple = (),
):
    """Publish routing + retained-replay match as ONE device program.

    A batch that carries wildcard SUBSCRIBEs used to pay two launch+
    readback trains: the route step for the publish rows, then one
    `_retained_step` launch per retained chunk for the replay storm
    (models/retained_index.py). This kernel runs both halves in the same
    jitted program — the storm's filter tables (a small one-off shape
    index) and one retained-topic chunk ride the route launch, and the
    [chunk, lanes] match matrix rides the same coalesced readback. The
    retained half is bit-identical to `_retained_step`: lengths derive
    on-device (retained topics cannot contain NUL), result narrows to
    int16 when the storm's fid space fits.
    """
    out = shape_route_step_impl(
        shape_tables,
        nfa_tables,
        sub_bitmaps,
        bytes_mat,
        lengths,
        group_tables,
        client_hash,
        topic_hash,
        rand,
        sem_tables,
        q_vecs,
        rule_feats,
        rule_valid,
        m_active=m_active,
        with_nfa=with_nfa,
        salt=salt,
        max_levels=max_levels,
        frontier=frontier,
        max_matches=max_matches,
        probes=probes,
        shape_probes=shape_probes,
        with_groups=with_groups,
        share_strategy=share_strategy,
        kslot=kslot,
        kg=kg,
        sem_topk=sem_topk,
        rule_progs=rule_progs,
    )
    rl = jnp.sum((ret_bytes != 0).astype(jnp.int32), axis=1)
    rout = shape_route_step_impl(
        ret_shape_tables,
        ret_nfa_tables,
        None,
        ret_bytes,
        rl,
        m_active=ret_m_active,
        with_nfa=ret_with_nfa,
        salt=ret_salt,
        max_levels=ret_max_levels,
    )
    rm = rout["matched"]
    out["retained"] = rm.astype(jnp.int16) if ret_narrow else rm
    return out


fused_route_retained_step = device_contract(
    "fused_route_retained_step",
    # single-device fusion: still no collectives, and the route half's
    # compact outputs keep their O(B*Kslot) bound
    collectives=(),
    out_bounds={
        "slots": lambda cfg: cfg["B"] * cfg["kslot"] * 4,
        "slot_count": lambda cfg: cfg["B"] * 4,
    },
)(partial(
    jax.jit,
    static_argnames=(
        "m_active",
        "with_nfa",
        "salt",
        "max_levels",
        "frontier",
        "max_matches",
        "probes",
        "shape_probes",
        "with_groups",
        "share_strategy",
        "kslot",
        "kg",
        "sem_topk",
        "rule_progs",
        "ret_m_active",
        "ret_with_nfa",
        "ret_salt",
        "ret_max_levels",
        "ret_narrow",
    ),
    donate_argnames=("lengths",),
)(fused_route_retained_step_impl))


STRATEGY_IDS = {
    "random": 0,
    "round_robin": 1,
    "sticky": 2,
    "hash_clientid": 3,
    "hash_topic": 4,
}


class GroupTable:
    """$share groups as device lane segments (SURVEY hard part (d)).

    Host registry mapping (real filter, group name) -> gid, mirrored on
    device as:
      ``filter_groups [Fcap, GPF]`` int32 — group ids per filter (-1 pad)
      ``group_len     [Gcap]``      int32 — member count per group
      ``group_rr      [Gcap]``      int32 — round-robin base (synced once
                                            per batch, not per message)
      ``group_sticky  [Gcap]``      int32 — sticky member index (-1 unset)

    The kernel picks a member INDEX per (topic, group); the host resolves
    index -> member and keeps only ack/retry failover
    (emqx_shared_sub.erl:234-285 pick semantics on-device).
    Implements the epoch/oplog/device_snapshot contract DeviceDeltaSync
    expects, same as SubscriberTable.
    """

    def __init__(self, gpf: int = 4):
        self.gpf = gpf
        self._fcap = 64
        self._gcap = 64
        self.filter_groups = np.full((self._fcap, self.gpf), -1, np.int32)
        self.group_len = np.zeros(self._gcap, np.int32)
        self.group_rr = np.zeros(self._gcap, np.int32)
        self.group_sticky = np.full(self._gcap, -1, np.int32)
        self._gids: Dict = {}  # (real, gname) -> gid
        self._info: Dict[int, tuple] = {}  # gid -> (real, gname)
        self._free: List[int] = []
        self._next_gid = 0
        self.epoch = 0
        self.oplog: list = []
        self.version = 0
        self.OPLOG_MAX = 65536

    def _bump(self) -> None:
        self.epoch += 1
        self.oplog.clear()
        self.version += 1

    def _log(self, name: str, flat_idx: int, val: int) -> None:
        self.version += 1
        if len(self.oplog) >= self.OPLOG_MAX:
            self._bump()
            return
        self.oplog.append((name, flat_idx, val))

    def _grow_fcap(self, need: int) -> None:
        nf = max(self._fcap, _next_pow2(need))
        if nf != self._fcap:
            new = np.full((nf, self.gpf), -1, np.int32)
            new[: self._fcap] = self.filter_groups
            self.filter_groups = new
            self._fcap = nf
            self._bump()

    def _grow_gpf(self) -> None:
        new = np.full((self._fcap, self.gpf * 2), -1, np.int32)
        new[:, : self.gpf] = self.filter_groups
        self.filter_groups = new
        self.gpf *= 2
        self._bump()

    def _grow_gcap(self) -> None:
        ng = self._gcap * 2
        for name in ("group_len", "group_rr", "group_sticky"):
            arr = getattr(self, name)
            fill = -1 if name == "group_sticky" else 0
            new = np.full(ng, fill, arr.dtype)
            new[: self._gcap] = arr
            setattr(self, name, new)
        self._gcap = ng
        self._bump()

    # -- membership ---------------------------------------------------------
    def ensure_group(self, fid: int, real: str, gname: str) -> int:
        key = (real, gname)
        gid = self._gids.get(key)
        if gid is not None:
            return gid
        if self._free:
            gid = self._free.pop()
        else:
            gid = self._next_gid
            self._next_gid += 1
        while gid >= self._gcap:
            self._grow_gcap()
        self._gids[key] = gid
        self._info[gid] = key
        # reset through the log so a recycled gid's device row resets too
        for name, val in (
            ("group_len", 0),
            ("group_rr", 0),
            ("group_sticky", -1),
        ):
            getattr(self, name)[gid] = val
            self._log(name, gid, val)
        self._grow_fcap(fid + 1)
        row = self.filter_groups[fid]
        slot = int(np.argmax(row < 0)) if (row < 0).any() else -1
        if slot < 0 or row[slot] >= 0:
            self._grow_gpf()
            row = self.filter_groups[fid]
            slot = int(np.argmax(row < 0))
        self.filter_groups[fid, slot] = gid
        self._log("filter_groups", fid * self.gpf + slot, gid)
        return gid

    def set_len(self, gid: int, n: int) -> None:
        if self.group_len[gid] != n:
            self.group_len[gid] = n
            self._log("group_len", gid, n)

    def set_rr(self, gid: int, v: int) -> None:
        v &= 0x7FFFFFFF
        if self.group_rr[gid] != v:
            self.group_rr[gid] = v
            self._log("group_rr", gid, v)

    def set_sticky(self, gid: int, idx: int) -> None:
        if self.group_sticky[gid] != idx:
            self.group_sticky[gid] = idx
            self._log("group_sticky", gid, idx)

    def repin(self, gid: int, member_sids, sticky_sid) -> None:
        """Recompute the device sticky index from the pinned sid (the
        ONE place the sid->index mapping convention lives; membership
        changes shift indices, so a raw index cannot be kept)."""
        sids = list(member_sids)
        if sticky_sid in sids:
            self.set_sticky(gid, sids.index(sticky_sid))
        else:
            self.set_sticky(gid, -1)

    def drop_group(self, fid: int, real: str, gname: str) -> None:
        gid = self._gids.pop((real, gname), None)
        if gid is None:
            return
        self._info.pop(gid, None)
        self._free.append(gid)
        self.group_len[gid] = 0
        self._log("group_len", gid, 0)
        if fid < self._fcap:
            row = self.filter_groups[fid]
            for slot in np.nonzero(row == gid)[0]:
                self.filter_groups[fid, slot] = -1
                self._log("filter_groups", fid * self.gpf + int(slot), -1)

    def gid_of(self, real: str, gname: str):
        return self._gids.get((real, gname))

    def info(self, gid: int):
        return self._info.get(gid)

    def pack_fcap(self, filter_capacity: int) -> None:
        if filter_capacity > self._fcap:
            self._grow_fcap(filter_capacity)

    def device_snapshot(self):
        return {
            "filter_groups": self.filter_groups,
            "group_len": self.group_len,
            "group_rr": self.group_rr,
            "group_sticky": self.group_sticky,
        }

    def __len__(self) -> int:
        return len(self._gids)


def _occurrence_index(flat_gids):
    """occ[i] = #{j < i : g[j] == g[i]} in flat (batch-major) order — the
    per-batch round-robin offset. Stable argsort groups equal gids while
    preserving arrival order; run positions come from a cummax of run
    starts; scatter restores original order."""
    n = flat_gids.shape[0]
    order = jnp.argsort(flat_gids, stable=True)
    sg = flat_gids[order]
    idx = jnp.arange(n, dtype=jnp.int32)
    new_seg = jnp.concatenate(
        [jnp.ones((1,), bool), sg[1:] != sg[:-1]]
    )
    seg_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(new_seg, idx, 0)
    )
    run_pos = idx - seg_start
    return jnp.zeros(n, jnp.int32).at[order].set(run_pos)


def share_pick_device(
    group_tables,
    matched,
    client_hash,
    topic_hash,
    rand,
    *,
    strategy: int,
    dp_axis: Optional[str] = None,
):
    """Resolve $share picks on-device: matched fids -> group lanes ->
    member index per strategy (emqx_shared_sub.erl:234-285 on the MXU-
    adjacent path). Returns (pick_gid [B,P], pick_idx [B,P]), -1 holes.

    strategy: STRATEGY_IDS value (static — each strategy is its own
    compiled program; brokers run one strategy at a time).

    `dp_axis`: when running INSIDE shard_map with the batch sharded over
    a mesh axis, round_robin's per-batch occurrence index must count
    occurrences across ALL shards, not just the local rows — otherwise
    every shard re-picks from the same synced base. The exact global
    offset comes from a per-group histogram all_gather over the axis:
    shard s adds sum of counts from shards < s (a segmented exclusive
    scan over ICI; one [dp, Gcap] all_gather per batch).
    """
    fg = group_tables["filter_groups"]
    glen = group_tables["group_len"]
    B, K = matched.shape
    gpf = fg.shape[1]
    safe = jnp.maximum(matched, 0)
    gids = fg[safe]  # [B, K, GPF]
    valid = (matched >= 0)[:, :, None] & (gids >= 0)
    gids = jnp.where(valid, gids, -1).reshape(B, K * gpf)
    gsafe = jnp.maximum(gids, 0)
    lens = glen[gsafe]
    denom = jnp.maximum(lens, 1)
    if strategy == 1:  # round_robin: per-batch occurrence + synced base
        occ = _occurrence_index(gids.reshape(-1)).reshape(B, -1)
        if dp_axis is not None:
            Gcap = glen.shape[0]
            ones = (gids >= 0).astype(jnp.int32).reshape(-1)
            counts = jnp.zeros(Gcap, jnp.int32).at[
                gsafe.reshape(-1)
            ].add(ones, mode="drop")
            all_c = jax.lax.all_gather(counts, dp_axis)  # [dp, Gcap]
            rank = jax.lax.axis_index(dp_axis)
            ndp = all_c.shape[0]
            prev = jnp.sum(
                jnp.where(
                    (jnp.arange(ndp) < rank)[:, None], all_c, 0
                ),
                axis=0,
            )  # [Gcap] occurrences in earlier shards
            occ = occ + prev[gsafe]
        idx = (group_tables["group_rr"][gsafe] + occ) % denom
    elif strategy == 2:  # sticky: stored index, random fallback
        st = group_tables["group_sticky"][gsafe]
        fallback = (
            (rand[:, None].astype(jnp.uint32) ^ gsafe.astype(jnp.uint32))
            % denom.astype(jnp.uint32)
        ).astype(jnp.int32)
        idx = jnp.where((st >= 0) & (st < lens), st, fallback)
    elif strategy == 3:  # hash_clientid
        idx = (
            client_hash[:, None].astype(jnp.uint32)
            % denom.astype(jnp.uint32)
        ).astype(jnp.int32)
    elif strategy == 4:  # hash_topic
        idx = (
            topic_hash[:, None].astype(jnp.uint32)
            % denom.astype(jnp.uint32)
        ).astype(jnp.int32)
    else:  # random: per-message entropy decorrelated across groups
        mixed = rand[:, None].astype(jnp.uint32) * jnp.uint32(
            2654435761
        ) ^ gsafe.astype(jnp.uint32)
        idx = (mixed % denom.astype(jnp.uint32)).astype(jnp.int32)
    ok = (gids >= 0) & (lens > 0)
    return jnp.where(ok, gids, -1), jnp.where(ok, idx, -1)


def _popcount_u32(arr: np.ndarray) -> int:
    """Total set bits of a uint32 array (chunked: no 8x byte blowup)."""
    bc = getattr(np, "bitwise_count", None)
    if bc is not None:
        return int(bc(arr).sum())
    total = 0
    flat = arr.reshape(-1).view(np.uint8)
    step = 1 << 22
    for lo in range(0, len(flat), step):
        total += int(np.unpackbits(flat[lo : lo + step]).sum())
    return total


class SubscriberTable:
    """Host-side registry: (filter id, subscriber slot) -> fan-out state,
    in one of TWO device representations behind one mutation interface:

    - **dense** (the original): a ``sub_bitmaps [Fcap, W]`` uint32
      matrix — O(Fcap * W) memory, one gather+OR per batch row. Right
      for small tables and shared-heavy/high-occupancy workloads;
    - **sparse** (ops/csr_table.py): per-fid CSR slot lists —
      O(total subscriptions) memory, the representation that makes a
      million DISTINCT single-subscriber topics (and the 100M-sub mesh
      run) physically possible.

    ``mode`` is the `router.sub_table` policy: ``dense`` pins the
    matrix (the degrade fallback), ``sparse`` converts immediately, and
    ``auto`` starts dense and flips ONCE (grow-only, checked at growth
    events so the per-subscribe cost is zero) when the matrix passes
    `AUTO_MIN_DENSE_BYTES` and exceeds `AUTO_RATIO` x the estimated CSR
    footprint — i.e. when occupancy x width says the bitmap is mostly
    zeros. A flip is an ordinary epoch bump on the SAME object: every
    holder (Broker, DeviceRouter, segment manager) just sees a full
    resync with the other representation's arrays.

    The reference keeps subscribers in per-node ETS bag tables
    (emqx_broker.erl:98-110); both representations op-log their scalar
    writes (flat index) so `DeviceSegmentManager` replays churn as
    O(delta) scatters, and growth/flips bump `epoch` (full re-upload).
    """

    AUTO_MIN_DENSE_BYTES = 8 << 20  # don't bother below 8MB dense
    AUTO_RATIO = 2.0  # flip when dense > ratio x estimated CSR bytes

    def __init__(self, max_subscribers: int = 1024, mode: str = "dense",
                 shards: int = 1):
        self.width_words = max(2, _next_pow2((max_subscribers + 31) // 32))
        self._fcap = 64
        self.arr = np.zeros((self._fcap, self.width_words), dtype=np.uint32)
        self.epoch = 0
        self.oplog: list = []  # (name, flat_idx, value)
        self.version = 0
        self.OPLOG_MAX = 65536
        self.mode = "dense"
        self.shards = max(1, int(shards))
        self._sp: Optional[CsrTable] = None  # the sparse rep when active
        self.live = 0  # live subscriptions (both reps; drives the policy)
        self.flips = 0
        if mode != "dense":
            self.set_mode(mode)

    # -- op-log plumbing (shared by both representations) ------------------
    def _bump_epoch(self) -> None:
        self.epoch += 1
        self.oplog.clear()
        self.version += 1

    def _log_any(self, name: str, flat_idx: int, val: int) -> None:
        self.version += 1
        if len(self.oplog) >= self.OPLOG_MAX:
            self._bump_epoch()
            return
        self.oplog.append((name, int(flat_idx), int(val)))

    def _log_resync(self, name: str) -> None:
        self.version += 1
        if len(self.oplog) >= self.OPLOG_MAX:
            self._bump_epoch()
            return
        from emqx_tpu.ops.segments import RESYNC

        self.oplog.append((RESYNC, name, 0))

    def _log(self, fid: int, w: int, val: int) -> None:
        self._log_any("sub_bitmaps", fid * self.width_words + w, val)

    # -- representation policy ---------------------------------------------
    @property
    def sparse(self) -> bool:
        return self._sp is not None

    @property
    def csr(self) -> Optional[CsrTable]:
        return self._sp

    def set_mode(self, mode: str) -> None:
        """Pin the representation policy; converts immediately when the
        pinned representation differs from the live one."""
        if mode not in ("auto", "dense", "sparse"):
            raise ValueError(f"sub_table mode {mode!r}")
        self.mode = mode
        if mode == "sparse" and self._sp is None:
            self._flip_sparse()
        elif mode == "dense" and self._sp is not None:
            self._flip_dense()

    def set_shards(self, shards: int) -> None:
        """Partition count for the mesh placement ('tp' slices of the
        slot column). Re-shards a live sparse table (epoch bump)."""
        shards = max(1, int(shards))
        if shards == self.shards:
            return
        self.shards = shards
        if self._sp is not None:
            self._sp.reshard(shards)

    def _csr_estimate(self) -> int:
        """Estimated CSR footprint: 4B slot column + 2 x 4B region lanes
        per fid + the hot segment floor."""
        return 16 * max(self.live, 1) + 8 * self._fcap + 8192

    def _maybe_flip(self) -> None:
        """Auto policy, checked only at dense growth events (the only
        times the answer can change): flip when occupancy x width says
        the matrix is mostly zeros AND it is big enough to matter."""
        if self.mode != "auto" or self._sp is not None:
            return
        dense_bytes = self.arr.nbytes
        if dense_bytes < self.AUTO_MIN_DENSE_BYTES:
            return
        if dense_bytes > self.AUTO_RATIO * self._csr_estimate():
            self._flip_sparse()

    def _mk_csr(self) -> CsrTable:
        return CsrTable(
            shards=self.shards,
            log=self._log_any,
            log_resync=self._log_resync,
            bump=self._bump_epoch,
        )

    def _flip_sparse(self) -> None:
        """dense -> CSR: expand the live bits (vectorized), build the
        exact-size CSR + registry, drop the matrix. One epoch bump."""
        rows, words = np.nonzero(self.arr)
        if len(rows):
            vals = self.arr[rows, words]
            bits = (
                (vals[:, None] >> np.arange(32, dtype=np.uint32)) & 1
            ).astype(bool)
            e_idx, e_bit = np.nonzero(bits)
            fids = rows[e_idx].astype(np.int64)
            slots = words[e_idx].astype(np.int64) * 32 + e_bit
        else:
            fids = slots = np.empty(0, np.int64)
        sp = self._mk_csr()
        built = CsrTable._build(fids, slots, sp.shards, self._fcap)
        sp._install(built)
        sp.max_slot = max(
            sp.max_slot, self.width_words * 32 - 1 if len(rows) else -1
        )
        self._sp = sp
        self.arr = None  # the matrix is gone — that is the point
        self.live = built["n"]
        self.flips += 1
        self._bump_epoch()

    def _flip_dense(self) -> None:
        """CSR -> dense (the degrade fallback / explicit pin)."""
        sp = self._sp
        fids, slots = sp.live_pairs()
        self._sp = None
        nf = max(64, _next_pow2(int(fids.max()) + 1 if len(fids) else 1))
        nw = max(
            self.width_words,
            _next_pow2((int(slots.max()) // 32 + 1) if len(slots) else 2),
        )
        self._fcap, self.width_words = nf, nw
        self.arr = np.zeros((nf, nw), np.uint32)
        if len(fids):
            w = slots // 32
            bits = (np.uint32(1) << (slots % 32).astype(np.uint32)).astype(
                np.uint32
            )
            np.bitwise_or.at(self.arr, (fids, w), bits)
        self.live = len(fids)
        self.flips += 1
        self._bump_epoch()

    # -- mutation (mode-dispatched) ----------------------------------------
    def _ensure(self, fid: int, slot: int) -> None:
        need_w = _next_pow2(slot // 32 + 1)
        need_f = _next_pow2(fid + 1)
        if need_w > self.width_words or need_f > self._fcap:
            nw = max(self.width_words, need_w)
            nf = max(self._fcap, need_f)
            new = np.zeros((nf, nw), dtype=np.uint32)
            new[: self._fcap, : self.width_words] = self.arr
            self.arr = new
            self.width_words = nw
            self._fcap = nf
            self._bump_epoch()
            self._maybe_flip()

    def _track_width(self, slot: int) -> None:
        # external readers size dense fallback rows from width_words;
        # keep it covering the slot universe in sparse mode too
        need_w = _next_pow2(slot // 32 + 1)
        if need_w > self.width_words:
            self.width_words = need_w

    def add(self, filter_id: int, slot: int) -> None:
        if self._sp is not None:
            if self._sp.add(filter_id, slot):
                self.live += 1
            self._fcap = max(self._fcap, self._sp._fcap)
            self._track_width(slot)
            return
        self._ensure(filter_id, slot)
        if self._sp is not None:  # _ensure's growth flipped the rep
            return self.add(filter_id, slot)
        w = slot // 32
        bit = np.uint32(1 << (slot % 32))
        if not self.arr[filter_id, w] & bit:
            self.live += 1
        self.arr[filter_id, w] |= bit
        self._log(filter_id, w, int(self.arr[filter_id, w]))

    def bulk_add(self, fids, slots) -> None:
        """Vectorized (fid, slot) load for cold starts; one epoch bump."""
        fids = np.asarray(fids, dtype=np.int64)
        slots = np.asarray(slots, dtype=np.int64)
        if not len(fids):
            return
        if self._sp is not None:
            self._sp.bulk_add(fids, slots)
            self.live = self._sp.live
            self._fcap = max(self._fcap, self._sp._fcap)
            self._track_width(int(slots.max()))
            return
        self._ensure(int(fids.max()), int(slots.max()))
        if self._sp is not None:
            return self.bulk_add(fids, slots)
        w = slots // 32
        bits = (np.uint32(1) << (slots % 32).astype(np.uint32)).astype(
            np.uint32
        )
        np.bitwise_or.at(self.arr, (fids, w), bits)
        self.live = _popcount_u32(self.arr)
        self._bump_epoch()
        self._maybe_flip()

    def remove(self, filter_id: int, slot: int) -> None:
        if self._sp is not None:
            if self._sp.remove(filter_id, slot):
                self.live -= 1
            return
        if filter_id >= self._fcap or slot // 32 >= self.width_words:
            return
        w = slot // 32
        bit = np.uint32(1 << (slot % 32))
        if self.arr[filter_id, w] & bit:
            self.live -= 1
        self.arr[filter_id, w] &= np.uint32(~bit & 0xFFFFFFFF)
        self._log(filter_id, w, int(self.arr[filter_id, w]))

    def pack(self, filter_capacity: int):
        """Grow to cover `filter_capacity` filter rows. Dense mode
        returns the live matrix (a view — valid until the next
        mutation); sparse mode returns None (there is no matrix)."""
        if self._sp is not None:
            # serve-time hot bound: a storm of adds with no background
            # compactor must not hand the kernel a giant hot scan
            self._sp.maybe_absorb()
            self._sp.pack(filter_capacity)
            self._fcap = max(self._fcap, self._sp._fcap)
            return None
        if filter_capacity > self._fcap:
            self._ensure(filter_capacity - 1, 0)
            if self._sp is not None:
                self._sp.pack(filter_capacity)
                return None
        return self.arr

    def device_snapshot(self):
        if self._sp is not None:
            return self._sp.device_snapshot()
        return {"sub_bitmaps": self.arr}

    # -- introspection (REST / gauges / benches) ---------------------------
    def fill_row_bits(self, fid: int, row: np.ndarray) -> None:
        """OR one fid's subscriber bits into a uint32 bitmap row — the
        host-built dense fallback for sparse overflow rows. Runs against
        the LIVE table (loop thread; the per-delivery filter re-verify
        is the staleness net, as everywhere on the dispatch path)."""
        if self._sp is not None:
            slots = self._sp.slots_of(fid)
            slots = slots[slots < len(row) * 32]
            if len(slots):
                np.bitwise_or.at(
                    row,
                    slots // 32,
                    (np.uint32(1) << (slots % 32).astype(np.uint32)).astype(
                        np.uint32
                    ),
                )
            return
        if fid < self._fcap:
            n = min(len(row), self.width_words)
            row[:n] |= self.arr[fid, :n]

    def table_bytes(self) -> int:
        """Device-table footprint of the ACTIVE representation — the
        `sub_table_bytes` number the memory-budget docs talk about."""
        if self._sp is not None:
            return self._sp.nbytes
        return int(self.arr.nbytes)

    def status(self) -> Dict:
        """Hotpath-REST / gauge block: mode, bytes, fill, tombstones."""
        out = {
            "mode": "sparse" if self._sp is not None else "dense",
            "policy": self.mode,
            "bytes": self.table_bytes(),
            "subscriptions": self.live,
            "width_words": self.width_words,
            "fcap": self._fcap,
            "flips": self.flips,
            "shards": self.shards,
        }
        if self._sp is not None:
            sp = self._sp
            out["csr_fill"] = sp.live
            out["csr_tombstones"] = sp.packed_tombs + sp.hot_tombs
            out["hot_fill"] = sp.hot_fill
            out["max_region"] = sp.max_region
        return out


class RouteResult(NamedTuple):
    """Host-side outputs of one routed batch (all numpy, device-free).

    Exactly ONE of the fan-out encodings is populated per row:

    - compact path (``slots is not None`` and not ``overflow[i]``):
      ``slots[i]`` holds the row's subscriber slot ids (-1 holes allowed
      anywhere — mesh serving concatenates per-shard segments);
    - dense path: ``bitmaps[i]`` (compaction off) or
      ``dense_rows[dense_index[i]]`` (compaction on, row overflowed the
      Kslot cap — the masked second transfer of the fallback contract).

    ``readback_bytes`` is the device->host transfer this batch actually
    paid (the `dispatch.readback.bytes` series).
    """

    matched: np.ndarray  # [B, K] sparse fids, -1 holes
    mcount: np.ndarray  # [B]
    flags: np.ndarray  # [B] host-must-fallback rows
    bitmaps: Optional[np.ndarray]  # [B, W] dense (None on compact path)
    picks: Optional[tuple]  # (pick_gid [B,P], pick_idx [B,P]) | None
    slots: Optional[np.ndarray] = None  # [B, Kslot] int32, -1 pad
    slot_count: Optional[np.ndarray] = None  # [B] total set bits (uncapped)
    overflow: Optional[np.ndarray] = None  # [B] bool: fanout > Kslot
    dense_rows: Optional[np.ndarray] = None  # [n_overflow, W] uint32
    dense_index: Optional[Dict[int, int]] = None  # batch row -> dense_rows row
    readback_bytes: int = 0
    # fused retained-replay storm that rode this batch's launch
    # (fused_route_retained_step): {filter: matched row-index array}
    retained: Optional[Dict[str, np.ndarray]] = None
    # fused session-ack stage outputs (session_route_step): a
    # `broker.session_store.SessionStepOut` — updated device mirror
    # (stays on device) + the O(sweep_k) sweep lists
    session: Optional[tuple] = None
    # semantic routing plane (docs/semantic_routing.md): qualifying
    # embedding-filter hits per row (UNCAPPED; winners are already
    # unioned into `slots`, so dispatch needs no extra decode)
    sem_count: Optional[np.ndarray] = None
    # compiled rule-predicate masks [R, B] bool, in DeviceRuleFilter
    # order (rules/compile.py) — consumed by the settle-time rule fire
    rule_masks: Optional[np.ndarray] = None


class _LazyDenseRows:
    """Dense fallback rows for SPARSE overflow rows, built on demand.

    The CSR path has no device bitmap matrix to gather overflow rows
    from, so the fallback unions the row's matched fids' slot lists
    from the HOST table instead. Construction here stores only the fid
    lists (cheap, runs on the dispatch executor); the actual union runs
    at `__getitem__` time — which is `Broker._dispatch_device_results`,
    on the event loop, the thread that owns the table — so no cross-
    thread reads of live arrays ever happen. Duck-types the
    `dense_rows[j]` indexing of the device-gathered overflow contract;
    nothing crossed the link for these rows (readback_bytes excludes
    them honestly).
    """

    __slots__ = ("subtab", "fid_lists")

    def __init__(self, subtab, fid_lists):
        self.subtab = subtab
        self.fid_lists = fid_lists

    def __len__(self) -> int:
        return len(self.fid_lists)

    def __getitem__(self, j: int) -> np.ndarray:
        row = np.zeros(self.subtab.width_words, np.uint32)
        for fid in self.fid_lists[j]:
            self.subtab.fill_row_bits(int(fid), row)
        return row


# prepared-args tuple layout (DeviceRouter._device_args_dirty): the
# clean-path Kslot recheck swaps one element in place, so the position
# is a named constant instead of a fragile negative index
_ARGS_KSLOT = 7

# floor for the auto-sized compact-slot cap: below this the slot list is
# cheaper than the program bookkeeping either way, and a tiny cap would
# overflow constantly while the fanout histogram warms up
KSLOT_MIN = 64


class DeviceRouter:
    """Serving-path engine: owns the device mirrors of the shape index, the
    residual NFA tables, and the subscriber bitmaps; runs
    `shape_route_step` over host batches.

    This is what puts the flagship kernel on the broker's hot path (the
    reference analog is the emqx_router:match_routes + emqx_broker:subscribers
    pair every publish crosses, emqx_broker.erl:204-215). All three table
    sets sync via the delta-overlay protocol, so steady-state batches pay
    only the kernel launch plus the readback.
    """

    def __init__(
        self,
        index,
        subtab: Optional[SubscriberTable],
        config=None,
        grouptab: Optional[GroupTable] = None,
        share_strategy: str = "round_robin",
        mesh=None,
        metrics=None,
        semtab=None,
    ):
        """`mesh`: a jax.sharding.Mesh with ("dp", "tp") axes — when set,
        batches execute the SPMD dist_shape_route_step (tables replicated,
        topic batch sharded over dp, subscriber lanes over tp, stats
        psum'd over ICI; parallel/mesh.py). $share picks resolve on-device
        in mesh mode too: group tables ride replicated like the match
        tables, per-topic pick entropy shards with the batch, and
        round_robin's occurrence index is cross-shard exact (an
        all_gather histogram over 'dp'; share_pick_device dp_axis)."""
        import dataclasses

        from emqx_tpu.ops.matcher import MatcherConfig
        from emqx_tpu.ops.nfa import MAX_PROBES
        from emqx_tpu.ops.segments import DeviceSegmentManager

        self.index = index
        self.subtab = subtab  # None => match-only (no fan-out bitmaps)
        self.grouptab = grouptab  # None => host-side $share pick
        # SemanticTable (ops/semantic_table.py): embedding-filter
        # subscriptions riding the same launch; None / empty = the
        # semantic stage never traces (docs/semantic_routing.md)
        self.semtab = semtab
        self.mesh = mesh
        # hot-path flight recorder (router.* series); None = don't record
        self.metrics = metrics
        self.share_strategy = STRATEGY_IDS.get(share_strategy, 1)
        config = config or MatcherConfig()
        if config.probes < MAX_PROBES:
            config = dataclasses.replace(config, probes=MAX_PROBES)
        self.config = config
        if mesh is not None:
            # sharded-from-upload mirrors: the canonical mesh layout is
            # applied at the DeviceDeltaSync level, so subscribe/
            # unsubscribe churn stays O(delta) scatters on the mesh too
            # (jit propagates the placed sharding through the scatter)
            from emqx_tpu.parallel.mesh import (
                bitmap_placement,
                table_placement,
            )

            tplace = table_placement(mesh)
            self._table_placement = tplace
            self._bitmap_placement = bitmap_placement(mesh)
            self._shape_sync = DeviceSegmentManager(
                placement=tplace, free_retired=True, metrics=self.metrics, name="shapes"
            )
            self._nfa_sync = DeviceSegmentManager(
                placement=tplace, free_retired=True, metrics=self.metrics, name="nfa"
            )
            # group tables are replicated on the mesh like match tables
            self._group_sync = DeviceSegmentManager(
                placement=tplace, free_retired=True, metrics=self.metrics, name="groups"
            )
        else:
            self._table_placement = None
            self._bitmap_placement = None
            self._shape_sync = DeviceSegmentManager(
                free_retired=True, metrics=self.metrics, name="shapes"
            )
            self._nfa_sync = DeviceSegmentManager(
                free_retired=True, metrics=self.metrics, name="nfa"
            )
            self._group_sync = DeviceSegmentManager(
                free_retired=True, metrics=self.metrics, name="groups"
            )
        # the subscriber-table mirror follows the table's ACTIVE
        # representation: dense lanes shard over 'tp', a CSR table's
        # arrays shard their leading (slot-owner) axis over 'tp'. A
        # representation flip (router.sub_table=auto) swaps the manager
        # — an ordinary full resync under the new placement.
        self._bits_sparse = (
            subtab is not None and getattr(subtab, "sparse", False)
        )
        self._bits_sync = self._mk_bits_sync(self._bits_sparse)
        # semantic-table mirror: entries shard their leading slot-owner
        # axis over 'tp' (slot % shards — the CSR regime, so per-shard
        # semantic hits are global slot ids; parallel/mesh.py)
        sem_place = None
        if mesh is not None and semtab is not None:
            from emqx_tpu.parallel.mesh import semantic_placement

            sem_place = semantic_placement(mesh)
        self._sem_sync = DeviceSegmentManager(
            placement=sem_place, free_retired=True, metrics=self.metrics, name="semantic"
        )
        # per-batch entropy seed; itertools.count's next() is atomic
        # under the GIL, keeping route_prepared free of shared mutable
        # state (it runs on executor threads)
        import itertools

        self._rand_seq = itertools.count(0xEC0)
        # auto-sized compact-slot cap (grow-only so the jit program is
        # stable; only _device_args — loop thread — mutates it)
        self._kslot = 0  # single-writer: loop
        # O(dirty) prepare: cached (version key, args) of the last
        # snapshot. While every source table's generation counter is
        # unchanged, prepare() returns this tuple without touching
        # pack/delta-sync at all — a clean-table batch costs a few dict
        # reads, not a re-walk of live structures. Only the loop thread
        # (prepare/_device_args callers) mutates it; `tpu-dispatch`
        # workers only ever see the immutable args tuple passed to
        # route_prepared (the publication pattern the CX checker's
        # single-writer declaration encodes — a pool-rooted writer
        # appearing later is a CX002)
        self._prep_key = None  # single-writer: loop
        self._prep_args = None  # single-writer: loop
        self._clean_streak = 0  # single-writer: loop
        # the residual walk's widths and what they were sized from:
        # (NFA table capacities, NfaBuilder.version, (frontier, matches))
        self._nfa_walk = None  # single-writer: loop

    def _mk_bits_sync(self, sparse: bool):
        from emqx_tpu.ops.segments import DeviceSegmentManager

        placement = None
        if self.mesh is not None:
            if sparse:
                from emqx_tpu.parallel.mesh import csr_placement

                placement = csr_placement(self.mesh)
            else:
                placement = self._bitmap_placement
        return DeviceSegmentManager(
            placement=placement, free_retired=True, metrics=self.metrics, name="bitmaps"
        )

    # clean-table prepares re-check the auto-sized Kslot only every this
    # many batches: the fanout histogram drifts slowly and the p99 scan
    # would otherwise be the only per-batch work left on the clean path
    KSLOT_RECHECK = 64

    def _fanout_kslot(self, width_words: int, sparse: bool = False,
                      semantic: bool = False) -> int:
        """Static Kslot for the next batch; 0 = compaction off.

        An explicit ``config.fanout_slots`` pins the cap (pow2-padded to
        avoid one recompile per odd value). Auto mode (0) sizes from the
        `dispatch.fanout` histogram p99 with 2x headroom, pow2-padded and
        GROW-ONLY — shrinking on a quiet period would recompile the
        serving program twice for zero readback win — and turns
        compaction off entirely while the slot universe (W*32) is no
        wider than the compact output would be.

        ``sparse``: a CSR table HAS no dense readback to fall back to —
        compaction is mandatory there, so the cap never returns 0 (and
        the fanout_compact knob / width win-condition don't apply).
        ``semantic``: the semantic union rides the compact slot rows
        (docs/semantic_routing.md), so an active semantic table makes
        the cap mandatory the same way.
        """
        cfg = self.config
        if self.subtab is None or (
            not sparse and not semantic and not cfg.fanout_compact
        ):
            return 0
        if cfg.fanout_slots > 0:
            return _next_pow2(cfg.fanout_slots)
        want = KSLOT_MIN
        if self.metrics is not None:
            h = self.metrics.histogram("dispatch.fanout")
            # 256 observations before trusting p99: the first batches
            # after boot are not a fan-out distribution yet
            if h is not None and h.count >= 256:
                want = max(want, 2 * max(1, int(h.p99)))
        k = max(self._kslot, _next_pow2(want))
        self._kslot = k
        if sparse or semantic:
            return k
        if self.mesh is not None:
            # per-shard compaction: each tp shard emits its own kslot-wide
            # list, so the win condition is against the LOCAL lane width
            width_words = max(1, width_words // self.mesh.shape["tp"])
        if k >= width_words * 32:
            return 0  # dense rows are already the smaller readback
        return k

    def _version_key(self):
        """Generation counters of every host table the snapshot is built
        from — equal keys mean the device mirrors are already current."""
        return (
            self.index.version,
            self.subtab.version if self.subtab is not None else -1,
            self.grouptab.version if self.grouptab is not None else -1,
            self.semtab.version if self.semtab is not None else -1,
        )

    def _device_args(self):
        # loop-side growth packs BEFORE the version key: the dirty sync
        # itself grows the bitmap/group tables to cover every live
        # filter id, and that (legitimate, same-thread) version bump
        # must not trip the torn-snapshot check below — filter-only
        # growth (e.g. a bulk route load) would fail its first prepare
        # spuriously. No-ops when capacities already cover the index.
        if self.subtab is not None:
            if (
                self.mesh is not None
                and self.subtab.sparse
                and self.subtab.shards != self.mesh.shape["tp"]
            ):
                # mesh attached after the representation flip (or the
                # app wiring was skipped): re-partition the slot column
                # over 'tp' BEFORE the version key, like any growth
                self.subtab.set_shards(self.mesh.shape["tp"])
            self.subtab.pack(self.index.num_filters_capacity)
        if self.grouptab is not None and len(self.grouptab):
            self.grouptab.pack_fcap(self.index.num_filters_capacity)
        key = self._version_key()
        if self._prep_key == key:
            # clean tables: skip pack/delta-sync entirely. The auto-sized
            # Kslot still gets a periodic re-check (traffic can grow the
            # fanout p99 without any table churn); growth only swaps the
            # cached tuple's kslot element — everything else is current.
            self._clean_streak += 1
            sem_on = self.semtab is not None and len(self.semtab) > 0
            if (
                self._clean_streak % self.KSLOT_RECHECK == 0
                and self.subtab is not None
                and (
                    self.config.fanout_compact
                    or self.subtab.sparse
                    or sem_on
                )
            ):
                kslot = self._fanout_kslot(
                    self.subtab.width_words,
                    sparse=self.subtab.sparse,
                    semantic=sem_on,
                )
                if kslot != self._prep_args[_ARGS_KSLOT]:
                    self._prep_args = (
                        self._prep_args[:_ARGS_KSLOT]
                        + (kslot,)
                        + self._prep_args[_ARGS_KSLOT + 1 :]
                    )
            if self.metrics is not None:
                self.metrics.inc("router.sync.skipped")
            return self._prep_args
        self._clean_streak = 0
        # Epoch discipline around the dirty sync (docs/robustness.md): a
        # pack/upload that raises — or tears (fault mode "corrupt": the
        # snapshot interleaves epochs) — must NEVER become the serving
        # snapshot. Roll back to the last good epoch (the generation
        # counters from the O(dirty) cache make "good" checkable) and
        # leave _prep_key stale so the next prepare retries the sync;
        # serving a slightly-stale-but-consistent table beats serving a
        # torn one, and beats taking the whole batch path down.
        try:
            action = _faults.hit("router.delta_sync")
            args = self._device_args_dirty()
            if action == "corrupt" or self._version_key() != key:
                raise RuntimeError(
                    "torn delta-sync: table generations moved during the "
                    "snapshot"
                )
        except Exception:
            if self._prep_args is None:
                raise  # no good epoch yet: the caller degrades to CPU
            if self.metrics is not None:
                self.metrics.inc("router.sync.rollback")
            return self._prep_args
        self._prep_key = key
        self._prep_args = args
        if self.metrics is not None:
            self.metrics.inc("router.prepare.dirty")
        self._trim_jit_cache()
        return args

    def _trim_jit_cache(self) -> None:
        """Bound the serving jits' compiled-program caches: every table
        growth / config transition compiles a fresh program keyed on the
        new shapes, and a long-lived broker must not accumulate every
        program it ever served. Runs only on dirty prepares — the clean
        path never recompiles."""
        lim = getattr(self.config, "jit_cache_max", 0)
        if lim <= 0:
            return
        for fn in (
            shape_route_step,
            shape_route_step_donated,
            fused_route_retained_step,
        ):
            try:
                size = fn._cache_size()
            except Exception:  # noqa: BLE001 — introspection best-effort
                continue
            if size > lim:
                fn.clear_cache()

    def _device_args_dirty(self):
        idx = self.index
        kg = 0
        sem_on = self.semtab is not None and len(self.semtab) > 0
        if self.subtab is not None:
            sparse = self.subtab.sparse
            if sparse != self._bits_sparse:
                # representation flip (router.sub_table policy): swap
                # the mirror manager so the full resync lands under the
                # new placement; the retired mirror frees with it
                self._bits_sync = self._mk_bits_sync(sparse)
                self._bits_sparse = sparse
                if self.metrics is not None:
                    self.metrics.inc("router.sparse.flips")
            # grow the fan-out table to cover every live filter id
            # BEFORE the snapshot — a matched fid must always gather a
            # real bitmap row / CSR region
            self.subtab.pack(idx.num_filters_capacity)
            if self.mesh is not None and not sparse:
                tp = self.mesh.shape["tp"]
                if self.subtab.width_words % tp:
                    # fail HERE with the config fix, before the sharded
                    # upload inside the delta sync raises an opaque
                    # NamedSharding divisibility error
                    raise ValueError(
                        f"subscriber bitmap width "
                        f"{self.subtab.width_words} not divisible by "
                        f"mesh tp={tp}; use a power-of-two tp"
                    )
            snap = self._bits_sync.sync(self.subtab)
            bits = snap if sparse else snap["sub_bitmaps"]
            kslot = self._fanout_kslot(
                self.subtab.width_words, sparse=sparse, semantic=sem_on
            )
            if sparse:
                kg = getattr(self.config, "sparse_gather", 0)
        else:
            bits = None
            kslot = 0
        shape_tables = self._shape_sync.sync(idx.shapes)
        with_nfa = idx.residual_count > 0
        nfa_tables = self._nfa_sync.sync(idx.nfa) if with_nfa else None
        if with_nfa:
            nfa_widths = self._nfa_widths(idx.nfa)
        else:  # no walk in the program: the widths are never traced
            nfa_widths = (self.config.frontier, self.config.max_matches)
        m_active = idx.shapes.m_active()
        if self.metrics is not None:
            self.metrics.gauge_set("route.shapes.active", m_active)
            self.metrics.gauge_set(
                "route.residual.filters", idx.residual_count
            )
            frontier, matches = nfa_widths if with_nfa else (0, 0)
            self.metrics.gauge_set("route.nfa.frontier_width", frontier)
            self.metrics.gauge_set("route.nfa.match_width", matches)
        if self.grouptab is not None and len(self.grouptab):
            self.grouptab.pack_fcap(idx.num_filters_capacity)
            fulls = self._group_sync.full_resyncs
            group_tables = self._group_sync.sync(self.grouptab)
            fulls = self._group_sync.full_resyncs - fulls
            if fulls and self.metrics is not None:
                # an epoch bump (growth, op-log overflow) since the last
                # launch: the group arrays went up whole, not as deltas
                self.metrics.inc("grouptab.uploads", fulls)
        else:
            group_tables = None
        if sem_on:
            # the semantic mirror rides the same sync machinery: full
            # upload on epoch bumps, op-logged scatter deltas otherwise
            sem_tables = self._sem_sync.sync(self.semtab)
            sem_topk = self.semtab.topk
        else:
            sem_tables = None
            sem_topk = 0
        return (
            shape_tables,
            nfa_tables,
            bits,
            idx.salt,
            m_active,
            with_nfa,
            group_tables,
            kslot,
            kg,
            sem_tables,
            sem_topk,
            nfa_widths,
        )

    def _nfa_widths(self, nfa):
        """-> (frontier, matches): the residual walk's static widths for
        this table, its exact bounds (`ops/nfa.walk_widths`) under the
        config's budgets, which stay the ceilings. A width only grows
        while the NFA tables keep their capacities, so a removal never
        compiles a program; a capacity change compiles anyway, and the
        widths are sized afresh."""
        from emqx_tpu.ops.nfa import walk_widths

        cap = (
            len(nfa.arr_edge_node), len(nfa.arr_plus),
            len(nfa.arr_vocab_sym),
        )
        last = self._nfa_walk
        if last is not None and last[:2] == (cap, nfa.version):
            return last[2]
        widths = walk_widths(
            nfa, self.config.frontier, self.config.max_matches
        )
        if last is not None and last[0] == cap:
            widths = tuple(max(a, b) for a, b in zip(widths, last[2]))
        self._nfa_walk = (cap, nfa.version, widths)
        return widths

    # -- segment maintenance (ops/segments.SegmentCompactor) --------------
    def segment_status(self) -> Dict:
        """Hot-segment occupancy + tombstone load of the serving tables —
        feeds the `router.segment.*` gauges and the compaction trigger."""
        sh = self.index.shapes
        return {
            "hot_fill": sh.hot_live,
            "hot_capacity": sh.hot_capacity,
            "tombstones": sh.packed_tombstones,
            "packed_capacity": sh._Tcap,
            "full_resyncs": self._shape_sync.full_resyncs,
            "delta_launches": self._shape_sync.delta_launches,
            "array_resyncs": self._shape_sync.array_resyncs,
        }

    def compaction_owners(self, hot_entries: int = 1024,
                          tombstone_frac: float = 0.25) -> list:
        """Adapters the background `SegmentCompactor` drives: merge the
        shape hot segment into the packed table, and proactively grow
        the subscriber bitmap matrix — both built + pre-uploaded on the
        compaction executor, applied on the loop, so the subscribe path
        never pays an O(table) rebuild or a full upload."""
        from emqx_tpu.ops.segments import (
            BitmapGrowthOwner,
            ShapeSegmentOwner,
        )

        owners = [
            ShapeSegmentOwner(
                self.index.shapes,
                self._shape_sync,
                placement=self._table_placement,
                hot_entries=hot_entries,
                tombstone_frac=tombstone_frac,
            )
        ]
        if self.subtab is not None and self.subtab.sparse:
            # CSR representation: merge the hot segment into the packed
            # slot column + purge tombstones (the ShapeIndex cycle);
            # built + pre-uploaded off the subscribe path
            placement = None
            if self.mesh is not None:
                from emqx_tpu.parallel.mesh import csr_placement

                placement = csr_placement(self.mesh)
            owners.append(
                CsrSegmentOwner(
                    self.subtab,
                    self._bits_sync,
                    placement=placement,
                    hot_entries=hot_entries,
                    tombstone_frac=tombstone_frac,
                )
            )
        elif self.subtab is not None:
            owners.append(
                BitmapGrowthOwner(
                    self.subtab,
                    self.index,
                    self._bits_sync,
                    placement=self._bitmap_placement,
                )
            )
        if self.semtab is not None:
            sem_place = None
            if self.mesh is not None:
                from emqx_tpu.parallel.mesh import semantic_placement

                sem_place = semantic_placement(self.mesh)
            owners.append(
                SemanticSegmentOwner(
                    self.semtab,
                    self._sem_sync,
                    placement=sem_place,
                    hot_entries=hot_entries,
                    tombstone_frac=tombstone_frac,
                )
            )
        return owners

    def prepare(self):
        """Snapshot + upload current tables/bitmaps. MUST run on the thread
        that mutates the index/subtab (the event loop): packing walks live
        Python structures. The returned tuple is immutable device state
        safe to hand to `route_prepared` on a worker thread."""
        import time

        t0 = time.perf_counter()
        args = self._device_args()
        if self.metrics is not None:
            self.metrics.observe(
                "router.sync.seconds", time.perf_counter() - t0
            )
        return args

    def route(self, topics, client_hashes=None, embeds=None, rules=None):
        """Batch route: returns a host-side `RouteResult` (all numpy)."""
        return self.route_prepared(
            self._device_args(), topics, client_hashes,
            embeds=embeds, rules=rules,
        )

    def route_prepared(self, args, topics, client_hashes=None,
                       retained=None, session=None, embeds=None,
                       rules=None):
        """Kernel launch + readback against a `prepare()` snapshot; touches
        no mutable host state, so it may run in an executor thread while
        the event loop keeps serving connections (the jit compile on a new
        batch/table shape can take tens of seconds on a real chip).

        `client_hashes` ([B] uint32, stable_hash of each publisher id)
        feeds the device $share pick; required only when a group table is
        loaded and the strategy is hash_clientid.

        `embeds` ([B, D] f32 per-message embeddings) feeds the fused
        semantic-match stage when the prepared args carry a semantic
        table (rows without an embedding ride a zero vector — matching
        nothing at any positive threshold). `rules` is an optional
        ``(progs, feats, valid)`` triple from rules/compile.
        DeviceRuleFilter: the compiled WHERE masks evaluate inside this
        same launch and land in `RouteResult.rule_masks`.

        `retained`: an optional prepared replay storm
        (DeviceRetainedIndex.prepare_storm) to fuse into this launch —
        chunk 0 rides the SAME program (fused_route_retained_step — or
        dist_fused_step on a `MeshServingRouter`) and the same readback;
        additional chunks (stores past 1M topics) launch alongside
        before any readback. Engines that cannot fuse advertise
        `supports_retained_fusion = False` and must not be handed a
        storm. The decoded {filter: rows} lands in
        `RouteResult.retained`. Returns a `RouteResult`.
        """
        import time

        t0 = time.perf_counter()
        # section `launch` (observe/profiler.py) opens here and is closed
        # by `_readback`, at the readback boundary
        _prof.begin("launch")
        try:
            out = self._route_prepared(
                args, topics, client_hashes, retained, session, embeds,
                rules, launch_open=True,
            )
        finally:
            if _prof.current_section() == "launch":
                _prof.end()  # raised before the readback boundary
        if self.metrics is not None:
            # Histogram.observe is lock-safe: this runs on executor threads
            wall = time.perf_counter() - t0
            self.metrics.observe("router.device.seconds", wall)
            self.metrics.observe("router.batch.size", len(topics))
            # cumulative link-bandwidth accounting (device_watch.py)
            self.metrics.inc("device.transfer.bytes", out.readback_bytes)
            if out.bitmaps is not None or out.slots is not None:
                self.metrics.observe(
                    "dispatch.readback.bytes", out.readback_bytes
                )
            if out.slots is not None:
                n_ovf = int(np.count_nonzero(out.overflow))
                self.metrics.inc(
                    "dispatch.compact.rows", len(topics) - n_ovf
                )
                if n_ovf:
                    self.metrics.inc(
                        "dispatch.compact.overflow.rows", n_ovf
                    )
        return out

    def _route_prepared(self, args, topics, client_hashes=None,
                        retained=None, session=None, embeds=None,
                        rules=None, launch_open=False):
        from emqx_tpu.broker.shared_sub import stable_hash
        from emqx_tpu.ops import tokenizer as tok

        # fault site: a failed tpu-dispatch launch (raise) or a slow one
        # (delay) — the broker's degradation ladder handles both
        _faults.hit("device.launch")
        cfg = self.config
        (
            shape_tables,
            nfa_tables,
            bits,
            salt,
            m_active,
            with_nfa,
            group_tables,
            kslot,
            kg,
            sem_tables,
            sem_topk,
            (frontier, max_matches),
        ) = args
        B = len(topics)
        Bp = max(64, _next_pow2(B))
        mat, lens, too_long = tok.encode_topics(list(topics), cfg.max_bytes)
        if Bp != B:
            mat = np.pad(mat, ((0, Bp - B), (0, 0)))
            lens = np.pad(lens, (0, Bp - B))
        with_groups = group_tables is not None
        if with_groups:
            # only the inputs this strategy reads are materialized — the
            # others are cheap zero vectors, not per-topic Python hashing
            ch = np.zeros(Bp, np.uint32)
            if client_hashes is not None:
                ch[:B] = np.asarray(client_hashes, np.uint32)
            if self.share_strategy == 4:  # hash_topic
                # TopicRef entries (zero-copy slab rows) decode here:
                # the pick hash is defined over the str form
                th = np.fromiter(
                    (
                        stable_hash(t if isinstance(t, str) else str(t))
                        for t in topics
                    ),
                    np.uint32,
                    count=B,
                )
                th = np.pad(th, (0, Bp - B))
            else:
                th = np.zeros(Bp, np.uint32)
            if self.share_strategy in (0, 2):  # random / sticky fallback
                rand = np.random.default_rng(
                    next(self._rand_seq)
                ).integers(0, 1 << 32, size=Bp, dtype=np.uint32)
            else:
                rand = np.zeros(Bp, np.uint32)
        else:
            ch = th = rand = None
        if sem_tables is not None:
            # per-message query embeddings, padded like the batch; rows
            # without one ride a zero vector (matches nothing at any
            # positive threshold)
            D = sem_tables["sem_vec"].shape[2]
            qv = np.zeros((Bp, D), np.float32)
            if embeds is not None:
                qv[:B] = np.asarray(embeds, np.float32)
        else:
            qv = None
        if rules is not None and rules[0]:
            rprogs, rf, rv = rules
            F = rf.shape[1]
            rfeats = np.zeros((Bp, F), np.float32)
            rfeats[:B] = rf
            rvalid = np.zeros((Bp, F), bool)
            rvalid[:B] = rv
        else:
            rprogs, rfeats, rvalid = (), None, None
        if self.mesh is not None and bits is not None:
            if session is not None:
                # engine contract: callers gate on
                # supports_session_fusion — the mesh engine's session
                # mirror updates ride the segment scatter path instead
                raise RuntimeError(
                    "session rider handed to a non-fusing mesh engine"
                )
            return self._route_mesh(
                shape_tables, nfa_tables, bits, salt, m_active, with_nfa,
                mat, lens, B, too_long, group_tables, ch, th, rand, kslot,
                retained=retained, kg=kg,
                sem_tables=sem_tables, sem_topk=sem_topk, qv=qv,
                rprogs=rprogs, rfeats=rfeats, rvalid=rvalid,
                launch_open=launch_open,
                nfa_widths=(frontier, max_matches),
            )
        step_kw = dict(
            m_active=m_active,
            with_nfa=with_nfa,
            salt=salt,
            max_levels=cfg.max_levels,
            frontier=frontier,
            max_matches=max_matches,
            probes=cfg.probes,
            with_groups=with_groups,
            share_strategy=self.share_strategy,
            kslot=kslot,
            kg=kg,
            sem_topk=sem_topk,
            rule_progs=rprogs,
        )
        if session is not None:
            # the fused session-ack stage: the rider's inflight writes +
            # retry/expiry sweep ride THIS launch and THIS readback (the
            # broker never pairs a rider with a retained storm)
            out = session_route_step(
                shape_tables, nfa_tables, bits, mat, lens,
                session.arrays, session.idxs, session.vals,
                session.clock,
                group_tables, ch, th, rand,
                sem_tables, qv, rfeats, rvalid,
                sweep_k=session.sweep_k, **step_kw,
            )
            return self._readback(
                out, B, too_long, with_groups, kslot, session=session,
                launch_open=launch_open, nfa_k=max_matches,
            )
        if retained is not None and retained.chunks:
            # one launch, one readback: the storm's chunk-0 match rides
            # the route program; extra chunks launch before any readback
            out = fused_route_retained_step(
                shape_tables, nfa_tables, bits, mat, lens,
                retained.shape_tables, retained.nfa_tables,
                retained.chunks[0],
                group_tables, ch, th, rand,
                sem_tables, qv, rfeats, rvalid,
                ret_m_active=retained.kwargs["m_active"],
                ret_with_nfa=retained.kwargs["with_nfa"],
                ret_salt=retained.kwargs["salt"],
                ret_max_levels=retained.kwargs["max_levels"],
                ret_narrow=retained.kwargs["narrow"],
                **step_kw,
            )
            from emqx_tpu.models.retained_index import _get_retained_step

            rstep = _get_retained_step()
            extra = [
                rstep(
                    retained.shape_tables, retained.nfa_tables, c,
                    **retained.kwargs,
                )
                for c in retained.chunks[1:]
            ]
            return self._readback(
                out, B, too_long, with_groups, kslot,
                retained=retained, extra_retained=extra,
                launch_open=launch_open, nfa_k=max_matches,
            )
        step = (
            shape_route_step_donated
            if getattr(cfg, "donate_buffers", False)
            else shape_route_step
        )
        out = step(
            shape_tables,
            nfa_tables,
            bits,
            mat,
            lens,
            group_tables,
            ch,
            th,
            rand,
            sem_tables,
            qv,
            rfeats,
            rvalid,
            **step_kw,
        )
        return self._readback(
            out, B, too_long, with_groups, kslot,
            launch_open=launch_open, nfa_k=max_matches,
        )

    # a launch's outputs are sliced on the device to the live rows rounded
    # up to this fraction of the launch's bucket, and trimmed on the host
    PULL_STEPS = 8

    def _pull(  # readback-site
        self, out, B, with_groups, kslot, mesh, retained, extra_retained,
        session,
    ):
        """The one coalesced `jax.device_get` of a batch's outputs, each
        sliced to the live rows.

        A slice of static length is a program of its own, compiled at its
        first use (~0.1 s): slicing to `B` itself met a new program for
        every batch size a bucket ever saw, all through a run (a cluster
        node's forwarded batches have any size). So the device slices to
        `B` rounded up to an eighth of the bucket (at most PULL_STEPS
        programs per bucket and output, at most an eighth more rows on the
        link) and the host trims the rest, which costs a view."""
        cap = out["matched"].shape[0]
        step = max(1, cap // self.PULL_STEPS)
        n = min(cap, -(-B // step) * step)
        rows = ["matched", "mcount", "flags"]
        if with_groups:
            rows += ["pick_gid", "pick_idx"]
        # sparse (CSR) fan-out: compact outputs exist with NO dense
        # bitmap matrix behind them — overflow rows rebuild on host
        sparse_fan = out["bitmaps"] is None and out.get("slots") is not None
        if out["bitmaps"] is not None or sparse_fan:
            if kslot:
                rows += ["slots", "slot_count"]
                if mesh:
                    rows.append("overflow")
            else:
                rows.append("bitmaps")
        if out.get("sem_count") is not None:
            # the semantic winners are already unioned into `slots`;
            # only the O(B) qualifying count crosses separately
            rows.append("sem_count")
        pulls = {k: out[k][:n] for k in rows}
        if out.get("rule_masks") is not None:
            pulls["rule_masks"] = out["rule_masks"][:, :n]
        if out.get("nfa_flagged") is not None:  # six ints, whole: no slice
            pulls["nfa_flagged"] = out["nfa_flagged"]
        if retained is not None:
            # the fused storm's chunk-0 match matrix rides the SAME
            # coalesced transfer as the route outputs; extra chunks
            # (launched alongside, no barrier) join the one device_get
            pulls["retained"] = out["retained"]
            for j, m in enumerate(extra_retained or ()):
                pulls[f"retained_{j + 1}"] = m
        if session is not None and session.sweep_k:
            # the session sweep's compact lists join the one device_get;
            # the updated table arrays themselves NEVER cross the link
            sess = out["session"]
            pulls["session_due"] = sess["due"]
            pulls["session_due_count"] = sess["due_count"]
            pulls["session_expired"] = sess["expired"]
            pulls["session_expired_count"] = sess["expired_count"]
        host = jax.device_get(pulls)
        if n != B:
            for k in rows:
                host[k] = host[k][:B]
            if "rule_masks" in host:
                host["rule_masks"] = host["rule_masks"][:, :B]
        return host

    def _readback(  # readback-site
        self, out, B, too_long, with_groups, kslot, mesh=False,
        retained=None, extra_retained=None, session=None,
        launch_open=False, *, nfa_k,
    ):
        """Pull one batch's outputs to host -> `RouteResult`.

        This is THE bandwidth boundary the compaction stage exists for:
        with ``kslot`` on, only the O(matches) compact arrays cross the
        link, plus one masked second transfer of the dense bitmap rows
        for the (overflow-flagged) rows the cap could not hold. Dense
        ``bitmaps`` rows of the full batch transfer only when compaction
        is off (or for match-only callers, never).

        Everything the batch needs crosses in ONE `jax.device_get` of a
        trimmed dict (sliced to the live rows): each separate `asarray`
        pull used to pay its own sync + RTT — eight of them per batch on
        the group+compact path — where one coalesced transfer pays one.
        Only the overflow fetch remains a (rare, masked) second
        transfer, because which rows need it is decided by `slot_count`,
        which must be on host first.

        ``mesh``: single-device overflow is derived on host from
        ``slot_count > kslot`` (one fewer array on the link); the mesh
        kernel's overflow is per-shard (any tp shard over its local cap)
        and must be read back.
        """
        # fault site: a wedged/failed device->host transfer (the other
        # half of the launch's round trip; same recovery ladder)
        _faults.hit("device.readback")
        # waterfall stages, each a profiler section of this (executor)
        # thread: `launch` = host encode + kernel enqueue up to here
        # (opened by `route_prepared`); `device_execute` = program
        # completion wait; `readback` = the coalesced device_get + host
        # decode. Per-batch perf_counter reads, nothing per-message.
        m = self.metrics
        if launch_open:
            launch_s = _prof.end()
            # the kernel is enqueued: the chip is lit until this launch's
            # device_execute ends (observe/profiler.py DarkLedger)
            _prof.dark_ledger.lit()
            if m is not None:
                m.observe("profile.stage.launch.seconds", launch_s)
        try:
            with _prof.section("device_execute") as sec:
                # the program's outputs complete together: waiting on one
                # output IS the device-execute boundary
                jax.block_until_ready(out["matched"])
        finally:
            if launch_open:
                _prof.dark_ledger.unlit()
        if m is not None:
            m.observe("profile.stage.device_execute.seconds", sec.seconds)
        _prof.begin("readback")
        try:
            host = self._pull(
                out, B, with_groups, kslot, mesh, retained,
                extra_retained, session,
            )
        finally:
            readback_s = _prof.end()
        if m is not None:
            m.observe("profile.stage.readback.seconds", readback_s)
        sparse_fan = out["bitmaps"] is None and out.get("slots") is not None
        matched = host["matched"]
        sem_count = host.get("sem_count")
        rule_masks = host.get("rule_masks")
        mcount = host["mcount"]
        flags = host["flags"] | too_long
        nfa_flagged = host.get("nfa_flagged")
        if nfa_flagged is not None and m is not None:
            # with_nfa: the residual engine's `nfa_k` columns come last
            nfa = matched[:, -nfa_k:]
            if flags.any():
                nfa = nfa[~flags]
            m.inc("route.nfa.matches", int(np.count_nonzero(nfa >= 0)))
            if nfa_flagged[0]:
                m.inc("route.nfa.flagged", int(nfa_flagged[0]))
                for cause, n in zip(NFA_FLAG_CAUSES, nfa_flagged[1:4]):
                    m.inc("route.nfa.flagged." + cause, int(n))
            for (_, name), n in zip(NFA_WALK_COUNTERS, nfa_flagged[4:]):
                m.inc(name, int(n))
        picks = (
            (host["pick_gid"], host["pick_idx"]) if with_groups else None
        )
        readback = 0
        for v in host.values():
            readback += v.nbytes
        retained_res = None
        if retained is not None:
            chunks_m = [host["retained"]] + [
                host[f"retained_{j + 1}"]
                for j in range(len(extra_retained or ()))
            ]
            retained_res = retained.decode(chunks_m)
        sess_res = None
        if session is not None:
            from emqx_tpu.broker.session_store import SessionStepOut

            sess = out["session"]
            if session.sweep_k:
                sess_res = SessionStepOut(
                    sess["tables"],
                    host["session_due"],
                    int(host["session_due_count"]),
                    host["session_expired"],
                    int(host["session_expired_count"]),
                )
            else:
                sess_res = SessionStepOut(sess["tables"], None, 0, None, 0)
        if out["bitmaps"] is None and not sparse_fan:
            return RouteResult(
                matched, mcount, flags, None, picks,
                readback_bytes=readback, retained=retained_res,
                session=sess_res, sem_count=sem_count,
                rule_masks=rule_masks,
            )
        if kslot:
            slots = host["slots"]
            slot_count = host["slot_count"]
            if mesh:
                overflow = host["overflow"]
            else:
                # holds on the sparse path too: the kernel forces
                # count past kslot for gather-window overflow rows
                overflow = slot_count > kslot
            dense_rows = dense_index = None
            ovf_idx = np.nonzero(overflow)[0]
            if ovf_idx.size:
                dense_index = {int(r): j for j, r in enumerate(ovf_idx)}
                if sparse_fan:
                    # no dense matrix exists: the fallback rows build
                    # lazily from the HOST table at dispatch time (on
                    # the loop thread — see _LazyDenseRows); nothing
                    # extra crosses the link
                    dense_rows = _LazyDenseRows(
                        self.subtab,
                        [
                            matched[r][matched[r] >= 0].tolist()
                            for r in ovf_idx
                        ],
                    )
                    if self.metrics is not None:
                        self.metrics.inc(
                            "router.sparse.overflow.rows",
                            int(ovf_idx.size),
                        )
                else:
                    # masked second transfer: ONLY the rows whose fan-
                    # out exceeded the cap come back dense
                    dense_rows = np.ascontiguousarray(
                        jax.device_get(out["bitmaps"][ovf_idx])
                    )
                    readback += dense_rows.nbytes
            return RouteResult(
                matched, mcount, flags, None, picks,
                slots=slots, slot_count=slot_count, overflow=overflow,
                dense_rows=dense_rows, dense_index=dense_index,
                readback_bytes=readback, retained=retained_res,
                session=sess_res, sem_count=sem_count,
                rule_masks=rule_masks,
            )
        # ascontiguousarray: a backend may hand back strided buffers,
        # and the dispatch path reinterprets rows as uint8
        bitmaps = np.ascontiguousarray(host["bitmaps"])
        return RouteResult(
            matched, mcount, flags, bitmaps, picks,
            readback_bytes=readback, retained=retained_res,
            session=sess_res, sem_count=sem_count,
            rule_masks=rule_masks,
        )

    # engine capability flag the broker gates storm fusion on: the
    # single-device engine fuses via fused_route_retained_step; a plain
    # DeviceRouter pointed at a mesh has no fused mesh program (that is
    # MeshServingRouter's job), so a storm must not be handed to it
    @property
    def supports_retained_fusion(self) -> bool:
        return self.mesh is None

    # session-ack fusion (session_route_step) is a single-device program;
    # the mesh engine's session mirrors update via the segment scatter
    # path on the 'dp'-sharded placement instead (docs/sessions.md)
    @property
    def supports_session_fusion(self) -> bool:
        return self.mesh is None

    def span_attrs(self) -> Dict:
        """Engine attributes stamped onto `router.device_step` spans."""
        return {}

    def _route_mesh(
        self, shape_tables, nfa_tables, bits, salt, m_active, with_nfa,
        mat, lens, B, too_long, group_tables=None, ch=None, th=None,
        rand=None, kslot=0, retained=None, kg=0, sem_tables=None,
        sem_topk=0, qv=None, rprogs=(), rfeats=None, rvalid=None,
        launch_open=False, *, nfa_widths,
    ):
        """SPMD serving: the batch rides dist_shape_route_step over the
        device mesh (SURVEY §2.4 TPU mapping; the multi-chip layout the
        dryrun gate compiles). Tables/bitmaps arrive ALREADY sharded —
        the sync mirrors upload straight into the canonical layout, so
        nothing is re-placed per batch; only the topic batch itself (and
        the per-topic $share pick entropy, which shards with it) is
        placed here."""
        from emqx_tpu.parallel.mesh import dist_shape_route_step, place_batch

        if retained is not None:
            # engine contract: callers gate on supports_retained_fusion.
            # Silently dropping the storm here would hang its waiters.
            raise RuntimeError(
                "retained storm handed to a non-fusing mesh engine; "
                "use MeshServingRouter for mesh serving"
            )
        cfg = self.config
        mat, lens, ch, th, rand, with_groups = self._mesh_pad(
            mat, lens, ch, th, rand, group_tables is not None
        )
        qv, rfeats, rvalid = self._mesh_pad_rows(mat, qv, rfeats, rvalid)
        st, nt, sb = shape_tables, nfa_tables, bits
        bm, ln = place_batch(self.mesh, mat, lens)
        out = dist_shape_route_step(
            self.mesh,
            st,
            nt,
            sb,
            bm,
            ln,
            group_tables,
            ch,
            th,
            rand,
            sem_tables,
            qv,
            rfeats,
            rvalid,
            m_active=m_active,
            salt=salt,
            max_levels=cfg.max_levels,
            frontier=nfa_widths[0],
            max_matches=nfa_widths[1],
            probes=cfg.probes,
            share_strategy=self.share_strategy,
            kslot=kslot,
            kg=kg,
            sem_topk=sem_topk,
            rule_progs=rprogs,
            donate=getattr(cfg, "donate_buffers", False),
        )
        return self._readback(
            out, B, too_long, with_groups, kslot, mesh=True,
            launch_open=launch_open,
            nfa_k=nfa_widths[1],
        )

    @staticmethod
    def _mesh_pad_rows(mat, qv, rfeats, rvalid):
        """Per-row semantic/rule operands pad to the dp-padded batch
        length the same way the $share entropy vectors do."""
        rows = mat.shape[0]
        if qv is not None and len(qv) != rows:
            qv = np.pad(qv, ((0, rows - len(qv)), (0, 0)))
        if rfeats is not None and len(rfeats) != rows:
            rfeats = np.pad(rfeats, ((0, rows - len(rfeats)), (0, 0)))
            rvalid = np.pad(rvalid, ((0, rows - len(rvalid)), (0, 0)))
        return qv, rfeats, rvalid

    def _mesh_pad(self, mat, lens, ch, th, rand, with_groups):
        """Round the batch up to a dp multiple (shard_map constraint) and
        keep the per-topic $share entropy vectors the same length.
        (Bitmap-width/tp divisibility is checked in _device_args, before
        the sharded upload; mat was already padded to a pow2 >= 64 — the
        extra rows here cover non-pow2 dp sizes.)"""
        dp = self.mesh.shape["dp"]
        rows = mat.shape[0]
        if rows % dp:
            extra = dp - rows % dp
            mat = np.pad(mat, ((0, extra), (0, 0)))
            lens = np.pad(lens, (0, extra))
        if with_groups and mat.shape[0] != (0 if ch is None else len(ch)):
            pad = mat.shape[0] - len(ch)
            ch = np.pad(ch, (0, pad))
            th = np.pad(th, (0, pad))
            rand = np.pad(rand, (0, pad))
        return mat, lens, ch, th, rand, with_groups

    def match_batch(
        self, topics: Sequence[str], fallback=None
    ) -> List[List[str]]:
        """Match topic strings -> matched filter names (no fan-out half).

        Flagged rows (too deep / NFA overflow) go to `fallback(topic)`.
        Each device hit is re-verified on host with a single-pair topic
        match before being returned: the shape path's 64-bit combined hash
        admits a ~2^-64 false positive, and a route decision (unlike local
        dispatch, which re-checks per delivery) would propagate it
        cluster-wide.
        """
        from emqx_tpu.ops import topics as T

        res = self.route(topics)
        matched, flags = res.matched, res.flags
        out: List[List[str]] = []
        for i, t in enumerate(topics):
            if flags[i]:
                if fallback is None:
                    # per-row error contract (ops/matcher.MatchError):
                    # one flagged row must not poison its batchmates
                    from emqx_tpu.ops.matcher import MatchError

                    out.append(MatchError(t))
                else:
                    out.append(fallback(t))
                continue
            row = matched[i]
            names = []
            for fid in row[row >= 0]:
                name = self.index.filter_name(int(fid))
                if name is not None and T.match(t, name):
                    names.append(name)
            out.append(names)
        return out


class MeshServingRouter(DeviceRouter):
    """The scale-out serving engine: `route_prepared` runs the SPMD dist
    step over a ('dp','tp') mesh as the broker's REAL dispatch engine —
    subscription table sharded over 'tp' (subscriber-lane slices), the
    ingest batch over 'dp', with retained-replay storms fused into the
    same sharded program (`dist_fused_step`). Everything the
    single-device engine earned is preserved by inheritance: the
    O(dirty) prepare cache, buffer donation, Kslot auto-sizing (against
    the per-shard lane width), the breaker/degrade ladder hooks, and the
    segment-manager upload path (all mirrors land pre-sharded via the
    placement hooks — nothing is re-placed per batch).

    `shard_label` names the mesh slice this process owns for span/
    metric attribution; a clustered node sets it to its advertised
    ('dp','tp') slice (cluster/route_sync.ShardOwnership), a standalone
    mesh broker keeps the default.
    """

    supports_retained_fusion = True

    def __init__(
        self,
        index,
        subtab: Optional[SubscriberTable],
        config=None,
        grouptab: Optional[GroupTable] = None,
        share_strategy: str = "round_robin",
        mesh=None,
        metrics=None,
        semtab=None,
    ):
        if mesh is None:
            raise ValueError("MeshServingRouter requires a ('dp','tp') mesh")
        super().__init__(
            index, subtab, config, grouptab=grouptab,
            share_strategy=share_strategy, mesh=mesh, metrics=metrics,
            semtab=semtab,
        )
        self.shard_label = "local"  # single-writer: loop

    def span_attrs(self) -> Dict:
        sh = self.mesh.shape
        return {
            "device.mesh_shape": f"{sh['dp']}x{sh['tp']}",
            "device.shard": self.shard_label,
        }

    def shard_status(self) -> Dict:
        """Per-tp-shard lane occupancy of the subscriber matrix — feeds
        the `mesh.shard.*` gauges. Nonzero WORDS (not bits): one pass of
        numpy counting, cheap enough for a housekeeping tick."""
        sh = dict(self.mesh.shape)
        out = {"dp": sh["dp"], "tp": sh["tp"], "shards": sh["dp"] * sh["tp"]}
        # what each device actually holds (table mirrors, all owners)
        held: Dict[int, int] = {}
        for mgr in (self._shape_sync, self._nfa_sync, self._bits_sync):
            for dev_id, n in mgr.device_bytes().items():
                held[dev_id] = held.get(dev_id, 0) + n
        out["device_bytes"] = held
        if self.subtab is not None and self.subtab.sparse:
            # CSR shards: per-'tp'-slice live-subscription counts (the
            # sparse lane-fill analog — exact, one pass over [S, F])
            sp = self.subtab.csr
            per = sp.csr_len.sum(axis=1)
            hot_live = (sp.hot_fid >= 0).sum(axis=1)
            fills = (per + hot_live).astype(np.float64)
            denom = max(1.0, float(fills.sum()))
            out["lane_fill_max"] = float(fills.max()) / denom
            out["lane_fill_min"] = float(fills.min()) / denom
            out["sub_table"] = "sparse"
            return out
        if self.subtab is not None:
            arr = self.subtab.arr
            tp = sh["tp"]
            w = arr.shape[1]
            per = w // tp if tp and w % tp == 0 else w
            fills = []
            for s in range(w // per if per else 0):
                sl = arr[:, s * per : (s + 1) * per]
                fills.append(
                    float(np.count_nonzero(sl)) / max(1, sl.size)
                )
            out["lane_fill_max"] = max(fills) if fills else 0.0
            out["lane_fill_min"] = min(fills) if fills else 0.0
        return out

    def _route_mesh(
        self, shape_tables, nfa_tables, bits, salt, m_active, with_nfa,
        mat, lens, B, too_long, group_tables=None, ch=None, th=None,
        rand=None, kslot=0, retained=None, kg=0, sem_tables=None,
        sem_topk=0, qv=None, rprogs=(), rfeats=None, rvalid=None,
        launch_open=False, *, nfa_widths,
    ):
        """SPMD serving with optional fused retained storm: chunk 0 of a
        prepared `StormJob` rides the SAME sharded program + readback
        (its rows scan sharded over 'dp'); extra chunks launch alongside
        before any readback — exactly the single-device fusion contract,
        spread over the mesh."""
        if retained is None or not retained.chunks:
            return super()._route_mesh(
                shape_tables, nfa_tables, bits, salt, m_active, with_nfa,
                mat, lens, B, too_long, group_tables, ch, th, rand, kslot,
                kg=kg, sem_tables=sem_tables, sem_topk=sem_topk, qv=qv,
                rprogs=rprogs, rfeats=rfeats, rvalid=rvalid,
                launch_open=launch_open, nfa_widths=nfa_widths,
            )
        from emqx_tpu.parallel.mesh import (
            dist_fused_route_step,
            place_batch,
        )

        cfg = self.config
        mat, lens, ch, th, rand, with_groups = self._mesh_pad(
            mat, lens, ch, th, rand, group_tables is not None
        )
        qv, rfeats, rvalid = self._mesh_pad_rows(mat, qv, rfeats, rvalid)
        bm, ln = place_batch(self.mesh, mat, lens)
        out = dist_fused_route_step(
            self.mesh,
            shape_tables,
            nfa_tables,
            bits,
            bm,
            ln,
            retained.shape_tables,
            retained.nfa_tables,
            retained.chunks[0],
            group_tables,
            ch,
            th,
            rand,
            sem_tables,
            qv,
            rfeats,
            rvalid,
            m_active=m_active,
            salt=salt,
            ret_m_active=retained.kwargs["m_active"],
            ret_with_nfa=retained.kwargs["with_nfa"],
            ret_salt=retained.kwargs["salt"],
            ret_max_levels=retained.kwargs["max_levels"],
            ret_narrow=retained.kwargs["narrow"],
            max_levels=cfg.max_levels,
            frontier=nfa_widths[0],
            max_matches=nfa_widths[1],
            probes=cfg.probes,
            share_strategy=self.share_strategy,
            kslot=kslot,
            kg=kg,
            sem_topk=sem_topk,
            rule_progs=rprogs,
            donate=getattr(cfg, "donate_buffers", False),
        )
        from emqx_tpu.models.retained_index import _get_retained_step

        rstep = _get_retained_step()
        extra = [
            rstep(
                retained.shape_tables, retained.nfa_tables, c,
                **retained.kwargs,
            )
            for c in retained.chunks[1:]
        ]
        return self._readback(
            out, B, too_long, with_groups, kslot, mesh=True,
            retained=retained, extra_retained=extra,
            launch_open=launch_open,
            nfa_k=nfa_widths[1],
        )
