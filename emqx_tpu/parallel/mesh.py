"""Mesh construction + the sharded distributed route step.

See package docstring for the axis semantics (dp = topic batch, tp =
subscriber bitmap lanes). The distributed step is `jax.shard_map` over the
mesh with XLA psum collectives for the global stats — the TPU-native
replacement for the reference's gen_rpc forwards + counter aggregation
(emqx_broker.erl:278-293, emqx_metrics.erl).
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from emqx_tpu.models.router_model import (
    compact_fanout_slots,
    shape_route_step_impl,
)
from emqx_tpu.ops.contract import device_contract

# built mesh step programs, registered for the device-watch compile
# probe (observe/device_watch.py): lru_cache hides its values, so the
# builders append their jitted fns here (bounded by the caches' maxsize)
_BUILT_PROGRAMS: list = []


def _register_built(fn):
    _BUILT_PROGRAMS.append(fn)
    return fn


def jit_cache_size() -> int:
    """Summed jit-cache entries across every built mesh step program —
    the mesh-path contribution to `device.compile.cache_size`."""
    n = 0
    for fn in _BUILT_PROGRAMS:
        cs = getattr(fn, "_cache_size", None)
        if cs is None:
            continue
        try:
            n += int(cs())
        except Exception:
            continue
    return n


def make_mesh(
    n_devices: Optional[int] = None,
    tp: Optional[int] = None,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Factor the first n devices into a ('dp', 'tp') mesh.

    tp defaults to 2 when n is even and > 1, else 1 — subscriber-lane
    sharding wants
    fewer, larger slices so each chip keeps big contiguous bitmap rows
    (HBM-bandwidth friendly), while dp soaks up the rest of the chips for
    batch throughput.
    """
    devs = list(devices if devices is not None else jax.devices())
    n = len(devs) if n_devices is None else n_devices
    if n > len(devs):
        raise ValueError(
            f"requested {n} devices but only {len(devs)} are available"
        )
    devs = devs[:n]
    if tp is None:
        tp = 2 if n % 2 == 0 and n > 1 else 1
    assert n % tp == 0, (n, tp)
    dp = n // tp
    arr = np.array(devs).reshape(dp, tp)
    return Mesh(arr, axis_names=("dp", "tp"))


# canonical output shardings + stats reduction, shared by both engines
def _out_specs(with_groups: bool = False, with_slots: bool = False,
               dense_bitmaps: bool = True, with_nfa: bool = False):
    specs = {
        "matched": P("dp", None),
        "mcount": P("dp"),
        "flags": P("dp"),
        # the CSR engine emits NO bitmap matrix: None here mirrors the
        # output dict's None leaf (empty pytree node on both sides)
        "bitmaps": P("dp", "tp") if dense_bitmaps else None,
        "stats": {"routed": P(), "matches": P(), "fanout_bits": P()},
    }
    if with_groups:
        specs["pick_gid"] = P("dp", None)
        specs["pick_idx"] = P("dp", None)
    if with_slots:
        # per-tp-shard compactions concatenate on the minor axis: the
        # global array is [B, kslot * tp] with -1 holes between shard
        # segments (the host filters >= 0, it never slices by count)
        specs["slots"] = P("dp", "tp")
        specs["slot_count"] = P("dp")
        specs["overflow"] = P("dp")
    if with_nfa:
        specs["nfa_flagged"] = P()
    return specs


def _sem_rules_local(out, sem_tables, qv, rfeats, rvalid, sem_topk,
                     rule_progs):
    """Per-shard semantic union + compiled-rule masks, shared by both
    serving builders. Runs INSIDE shard_map: `sem_tables` is this tp
    shard's slice of the entry axis (slot-owner sharding — winner slots
    are global ids, so the union lands before the 'tp' concat with no
    rebase); the qualifying counts psum over 'tp'. Rule feature rows
    ride the 'dp' batch shards and are tp-replicated, like `matched`."""
    if sem_tables is not None:
        from emqx_tpu.ops.semantic_table import (
            semantic_match_step,
            union_semantic_slots,
        )

        sem_slots, sem_count = semantic_match_step(
            sem_tables, qv, out["matched"], sem_topk
        )
        out["slots"] = union_semantic_slots(out["slots"], sem_slots)
        out["sem_count"] = jax.lax.psum(sem_count, "tp")
    if rule_progs:
        from emqx_tpu.rules.compile import eval_rule_masks

        out["rule_masks"] = eval_rule_masks(rule_progs, rfeats, rvalid)


def _reduce_stats(out, with_groups: bool = False):
    """routed/matches are identical across tp replicas: reduce over dp
    only. fanout_bits is partial per lane slice: reduce over both axes."""
    stats = out["stats"]
    out["stats"] = {
        "routed": jax.lax.psum(stats["routed"], "dp"),
        "matches": jax.lax.psum(stats["matches"], "dp"),
        "fanout_bits": jax.lax.psum(stats["fanout_bits"], ("dp", "tp")),
    }
    if "nfa_flagged" in out:  # like routed: identical across tp
        # the flagged counts sum over the batch shards; the walk's two
        # scalars (levels, frontier peak) are each shard's own: the max
        nf = out["nfa_flagged"]
        out["nfa_flagged"] = jnp.concatenate([
            jax.lax.psum(nf[:4], "dp"), jax.lax.pmax(nf[4:], "dp"),
        ])
    if not with_groups:
        out.pop("pick_gid", None)
        out.pop("pick_idx", None)
    return out


@device_contract(
    "dist_shape_step",
    kind="builder",
    # stats psum over ('dp','tp') + the kslot>0 per-shard compaction's
    # lane-offset rebase (axis_index) and count/overflow psum over 'tp';
    # with residual filters, the NFA walk's two scalars pmax over 'dp'
    collectives=("psum", "axis_index", "pmax"),
    out_bounds={
        # per-shard compaction concatenates over tp: [B, kslot * tp]
        "slots": lambda cfg: (
            cfg["B"] * cfg["kslot"] * cfg.get("tp", 1) * 4
        ),
        "slot_count": lambda cfg: cfg["B"] * 4,
    },
)
@lru_cache(maxsize=32)
def _dist_shape_step_fn(
    mesh: Mesh,
    shape_keys: tuple,
    nfa_keys: Optional[tuple],
    group_keys: Optional[tuple],
    share_strategy: int,
    m_active: int,
    salt: int,
    max_levels: int,
    frontier: int,
    max_matches: int,
    probes: int,
    kslot: int = 0,
    donate: bool = False,
    sub_keys: Optional[tuple] = None,
    kg: int = 0,
    sem_keys: Optional[tuple] = None,
    sem_topk: int = 0,
    rule_progs: tuple = (),
):
    """The SERVING engine (shape index + residual NFA + fan-out + $share
    pick) sharded over the mesh — same layout as `_dist_step_fn`, all
    table sets replicated; per-topic pick entropy (client/topic hashes,
    rand) rides the 'dp' shards with the batch, and round_robin's
    occurrence index is made globally exact via an all_gather histogram
    over 'dp' (share_pick_device dp_axis).

    ``kslot > 0`` adds the sparse fan-out compaction PER tp SHARD: each
    shard compacts its own bitmap lanes (local slot ids rebased by the
    shard's lane offset, so they are the same global slot ids the host
    uses), the per-shard slot lists concatenate over 'tp' in the output
    (-1 holes between segments), and count/overflow psum/OR over 'tp'.
    A row overflows when ANY shard's local fan-out exceeds kslot —
    conservative, and the host's dense fallback keeps it correct.

    ``sub_keys`` set = the CSR subscriber table (ops/csr_table.py):
    its arrays shard their leading slot-owner axis over 'tp'
    (`csr_placement`), each shard's `sparse_fanout_slots` emits GLOBAL
    slot ids directly (no lane rebase), and only the count psum /
    overflow OR run here. Same output contract either way.

    ``sem_keys`` set = the semantic table (ops/semantic_table.py):
    entries shard their leading slot-owner axis over 'tp'
    (`semantic_placement`, the CSR regime), each shard's
    `semantic_match_step` matmul answers its slice of the embedding
    filters against the dp-sharded query batch, and the winner slots
    (GLOBAL ids) union into the shard's compact rows before the 'tp'
    concat; the qualifying counts psum over 'tp'. ``rule_progs``
    evaluates the compiled WHERE masks over the dp-sharded feature
    batch (tp-replicated, like `matched`)."""
    with_nfa = nfa_keys is not None
    with_groups = group_keys is not None
    sparse = sub_keys is not None
    with_sem = sem_keys is not None

    def local_step(
        shape_tables, nfa_tables, group_tables, ch, th, rand,
        sub_bitmaps, bytes_mat, lengths, sem_tables, qv, rfeats, rvalid,
    ):
        out = shape_route_step_impl(
            shape_tables,
            nfa_tables,
            sub_bitmaps,
            bytes_mat,
            lengths,
            group_tables,
            ch,
            th,
            rand,
            m_active=m_active,
            with_nfa=with_nfa,
            salt=salt,
            max_levels=max_levels,
            frontier=frontier,
            max_matches=max_matches,
            probes=probes,
            with_groups=with_groups,
            share_strategy=share_strategy,
            dp_axis="dp" if with_groups else None,
            kslot=kslot if sparse else 0,
            kg=kg,
        )
        if kslot:
            if sparse:
                # per-shard CSR compaction already ran inside the impl;
                # reduce the per-shard counts/overflow over 'tp'
                out["slot_count"] = jax.lax.psum(out["slot_count"], "tp")
                out["overflow"] = (
                    jax.lax.psum(
                        out["overflow"].astype(jnp.int32), "tp"
                    )
                    > 0
                )
            else:
                slots, count, over = compact_fanout_slots(
                    out["bitmaps"], kslot
                )
                w_local = out["bitmaps"].shape[1]
                off = jax.lax.axis_index("tp").astype(jnp.int32) * (
                    w_local * 32
                )
                out["slots"] = jnp.where(slots >= 0, slots + off, -1)
                out["slot_count"] = jax.lax.psum(count, "tp")
                out["overflow"] = (
                    jax.lax.psum(over.astype(jnp.int32), "tp") > 0
                )
        _sem_rules_local(
            out, sem_tables, qv, rfeats, rvalid, sem_topk, rule_progs
        )
        return _reduce_stats(out, with_groups)

    shape_specs = {k: P() for k in shape_keys}
    nfa_specs = {k: P() for k in nfa_keys} if with_nfa else None
    group_specs = {k: P() for k in group_keys} if with_groups else None
    per_topic = P("dp") if with_groups else P()
    sub_spec = (
        {k: P("tp", None) for k in sub_keys}
        if sparse
        else P(None, "tp")
    )
    sem_specs = {k: P("tp") for k in sem_keys} if with_sem else None
    out_specs = _out_specs(
        with_groups, with_slots=kslot > 0,
        dense_bitmaps=not sparse, with_nfa=with_nfa,
    )
    if with_sem:
        out_specs["sem_count"] = P("dp")
    if rule_progs:
        out_specs["rule_masks"] = P(None, "dp")
    fn = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(
            shape_specs, nfa_specs, group_specs,
            per_topic, per_topic, per_topic,
            sub_spec, P("dp", None), P("dp"),
            sem_specs, P("dp", None), P("dp", None), P("dp", None),
        ),
        out_specs=out_specs,
    )
    # ``donate``: recycle the per-batch lengths buffer (aliases the
    # [B]-shaped int32 outputs under the same 'dp' sharding) — the mesh
    # twin of shape_route_step_donated; tables/bitmaps never donate.
    jit_kw = {"donate_argnums": (8,)} if donate else {}
    return _register_built(jax.jit(fn, **jit_kw))


@device_contract(
    "dist_fused_step",
    kind="builder",
    # the fused serving builder inherits dist_shape_step's ICI budget:
    # stats psums + the per-shard compaction's lane-offset rebase (and
    # the NFA walk's pmax, with residual filters). The
    # retained half is shard-local by construction (chunk rows ride
    # 'dp'; its tables are replicated) — a collective appearing there
    # is a contract violation, not a tuning knob.
    collectives=("psum", "axis_index", "pmax"),
    out_bounds={
        "slots": lambda cfg: (
            cfg["B"] * cfg["kslot"] * cfg.get("tp", 1) * 4
        ),
        "slot_count": lambda cfg: cfg["B"] * 4,
    },
)
@lru_cache(maxsize=32)
def _dist_fused_step_fn(
    mesh: Mesh,
    shape_keys: tuple,
    nfa_keys: Optional[tuple],
    group_keys: Optional[tuple],
    ret_shape_keys: tuple,
    ret_nfa_keys: Optional[tuple],
    share_strategy: int,
    m_active: int,
    salt: int,
    max_levels: int,
    frontier: int,
    max_matches: int,
    probes: int,
    kslot: int,
    ret_m_active: int,
    ret_with_nfa: bool,
    ret_salt: int,
    ret_max_levels: int,
    ret_narrow: bool,
    donate: bool = False,
    sub_keys: Optional[tuple] = None,
    kg: int = 0,
    sem_keys: Optional[tuple] = None,
    sem_topk: int = 0,
    rule_progs: tuple = (),
):
    """`_dist_shape_step_fn` + the retained-replay half fused into the
    SAME sharded program (the mesh analog of
    `fused_route_retained_step`): a wildcard-subscribe storm's filter
    tables ride replicated like the match tables, and the retained-topic
    chunk shards its ROWS over 'dp' — each dp slice matches its share of
    the stored topics, so the replay scan scales with the mesh instead
    of serializing on one chip. The [chunk, lanes] match matrix
    concatenates over 'dp' in the output and rides the same coalesced
    readback as the route outputs.

    ``donate``: donate the per-batch `lengths` buffer (aliases the
    [B]-shaped int32 outputs, same 'dp' sharding) — the mesh-path twin
    of `shape_route_step_donated`."""
    from emqx_tpu.models.router_model import shape_route_step_impl

    with_nfa = nfa_keys is not None
    with_groups = group_keys is not None
    sparse = sub_keys is not None
    with_sem = sem_keys is not None

    def local_step(
        shape_tables, nfa_tables, group_tables, ch, th, rand,
        sub_bitmaps, bytes_mat, lengths,
        ret_shape_tables, ret_nfa_tables, ret_bytes,
        sem_tables, qv, rfeats, rvalid,
    ):
        out = shape_route_step_impl(
            shape_tables,
            nfa_tables,
            sub_bitmaps,
            bytes_mat,
            lengths,
            group_tables,
            ch,
            th,
            rand,
            m_active=m_active,
            with_nfa=with_nfa,
            salt=salt,
            max_levels=max_levels,
            frontier=frontier,
            max_matches=max_matches,
            probes=probes,
            with_groups=with_groups,
            share_strategy=share_strategy,
            dp_axis="dp" if with_groups else None,
            kslot=kslot if sparse else 0,
            kg=kg,
        )
        if kslot:
            if sparse:
                out["slot_count"] = jax.lax.psum(out["slot_count"], "tp")
                out["overflow"] = (
                    jax.lax.psum(
                        out["overflow"].astype(jnp.int32), "tp"
                    )
                    > 0
                )
            else:
                slots, count, over = compact_fanout_slots(
                    out["bitmaps"], kslot
                )
                w_local = out["bitmaps"].shape[1]
                off = jax.lax.axis_index("tp").astype(jnp.int32) * (
                    w_local * 32
                )
                out["slots"] = jnp.where(slots >= 0, slots + off, -1)
                out["slot_count"] = jax.lax.psum(count, "tp")
                out["overflow"] = (
                    jax.lax.psum(over.astype(jnp.int32), "tp") > 0
                )
        _sem_rules_local(
            out, sem_tables, qv, rfeats, rvalid, sem_topk, rule_progs
        )
        # retained half: bit-identical to fused_route_retained_step's,
        # on this shard's slice of the chunk rows (lengths derive
        # on-device — retained topics cannot contain NUL)
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
        return _reduce_stats(out, with_groups)

    shape_specs = {k: P() for k in shape_keys}
    nfa_specs = {k: P() for k in nfa_keys} if with_nfa else None
    group_specs = {k: P() for k in group_keys} if with_groups else None
    ret_shape_specs = {k: P() for k in ret_shape_keys}
    ret_nfa_specs = (
        {k: P() for k in ret_nfa_keys} if ret_nfa_keys is not None else None
    )
    per_topic = P("dp") if with_groups else P()
    out_specs = _out_specs(
        with_groups, with_slots=kslot > 0, dense_bitmaps=not sparse,
        with_nfa=with_nfa,
    )
    out_specs["retained"] = P("dp", None)
    if with_sem:
        out_specs["sem_count"] = P("dp")
    if rule_progs:
        out_specs["rule_masks"] = P(None, "dp")
    sub_spec = (
        {k: P("tp", None) for k in sub_keys}
        if sparse
        else P(None, "tp")
    )
    sem_specs = {k: P("tp") for k in sem_keys} if with_sem else None
    fn = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(
            shape_specs, nfa_specs, group_specs,
            per_topic, per_topic, per_topic,
            sub_spec, P("dp", None), P("dp"),
            ret_shape_specs, ret_nfa_specs, P("dp", None),
            sem_specs, P("dp", None), P("dp", None), P("dp", None),
        ),
        out_specs=out_specs,
    )
    jit_kw = {"donate_argnums": (8,)} if donate else {}
    return _register_built(jax.jit(fn, **jit_kw))


# Second registry entry for the serving builder traced with the CSR
# subscriber table: the sparse mesh program replaces the dense per-shard
# compaction (which needs the axis_index lane rebase) with the in-impl
# CSR gather — its ICI budget is the stats/count psums ONLY. A lane
# rebase appearing in the sparse trace is a contract violation.
# Registry entry for the serving builder traced WITH a semantic table:
# the semantic union adds the per-shard similarity matmul + top-k and
# one more count psum to the program; the dense per-shard compaction's
# lane rebase (axis_index) stays. Its ICI budget is pinned here.
device_contract(
    "sem_dist_shape_step",
    kind="builder",
    collectives=("psum", "axis_index"),
    out_bounds={
        "slots": lambda cfg: (
            cfg["B"] * cfg["kslot"] * 2 * cfg.get("tp", 1) * 4
        ),
        "slot_count": lambda cfg: cfg["B"] * 4,
        "sem_count": lambda cfg: cfg["B"] * 4,
    },
)(_dist_shape_step_fn)

device_contract(
    "sparse_dist_shape_step",
    kind="builder",
    collectives=("psum",),
    out_bounds={
        "slots": lambda cfg: (
            cfg["B"] * cfg["kslot"] * cfg.get("tp", 1) * 4
        ),
        "slot_count": lambda cfg: cfg["B"] * 4,
    },
)(_dist_shape_step_fn)


def dist_fused_route_step(
    mesh: Mesh,
    shape_tables: Dict,
    nfa_tables: Optional[Dict],
    sub_bitmaps,
    bytes_mat,
    lengths,
    ret_shape_tables: Dict,
    ret_nfa_tables: Optional[Dict],
    ret_bytes,
    group_tables: Optional[Dict] = None,
    client_hash=None,
    topic_hash=None,
    rand=None,
    sem_tables: Optional[Dict] = None,
    q_vecs=None,
    rule_feats=None,
    rule_valid=None,
    *,
    m_active: int,
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
    share_strategy: int = 0,
    kslot: int = 0,
    donate: bool = False,
    kg: int = 0,
    sem_topk: int = 0,
    rule_progs: tuple = (),
):
    """Distributed serving step WITH a fused retained-replay storm —
    the mesh engine `MeshServingRouter.route_prepared` launches when a
    prepared `StormJob` rides the batch. Sharding as in
    `dist_shape_route_step`, plus: storm filter tables replicated,
    retained chunk rows on 'dp', the match matrix back on ('dp', None)."""
    fn = _dist_fused_step_fn(
        mesh,
        tuple(sorted(shape_tables)),
        tuple(sorted(nfa_tables)) if nfa_tables is not None else None,
        tuple(sorted(group_tables)) if group_tables is not None else None,
        tuple(sorted(ret_shape_tables)),
        tuple(sorted(ret_nfa_tables))
        if ret_nfa_tables is not None
        else None,
        share_strategy,
        m_active,
        salt,
        max_levels,
        frontier,
        max_matches,
        probes,
        kslot,
        ret_m_active,
        ret_with_nfa,
        ret_salt,
        ret_max_levels,
        ret_narrow,
        donate,
        tuple(sorted(sub_bitmaps))
        if isinstance(sub_bitmaps, dict)
        else None,
        kg,
        tuple(sorted(sem_tables)) if sem_tables is not None else None,
        sem_topk,
        rule_progs,
    )
    return fn(
        shape_tables, nfa_tables, group_tables, client_hash, topic_hash,
        rand, sub_bitmaps, bytes_mat, lengths,
        ret_shape_tables, ret_nfa_tables, ret_bytes,
        sem_tables, q_vecs, rule_feats, rule_valid,
    )


def dist_shape_route_step(
    mesh: Mesh,
    shape_tables: Dict,
    nfa_tables: Optional[Dict],
    sub_bitmaps,
    bytes_mat,
    lengths,
    group_tables: Optional[Dict] = None,
    client_hash=None,
    topic_hash=None,
    rand=None,
    sem_tables: Optional[Dict] = None,
    q_vecs=None,
    rule_feats=None,
    rule_valid=None,
    *,
    m_active: int,
    salt: int,
    max_levels: int = 16,
    frontier: int = 32,
    max_matches: int = 64,
    probes: int = 8,
    share_strategy: int = 0,
    kslot: int = 0,
    donate: bool = False,
    kg: int = 0,
    sem_topk: int = 0,
    rule_progs: tuple = (),
):
    """Distributed serving step (shape engine). Sharding: tables
    replicated (read-mostly; updates are host-pushed deltas), subscriber
    lanes on 'tp' (each chip owns a slice), topic batch on 'dp';
    matched/mcount/flags come back sharded over 'dp', bitmaps over
    ('dp','tp'), stats psum'd over ICI to replicated scalars. With
    `group_tables`, $share picks resolve on-device per dp shard (r3
    verdict item 4 — the host pick wall stays down on the multi-chip
    path too).
    ``kslot`` engages per-shard sparse fan-out compaction (see
    `_dist_shape_step_fn`). A dict `sub_bitmaps` = the CSR subscriber
    table, arrays sharded over 'tp' by their leading slot-owner axis."""
    fn = _dist_shape_step_fn(
        mesh,
        tuple(sorted(shape_tables)),
        tuple(sorted(nfa_tables)) if nfa_tables is not None else None,
        tuple(sorted(group_tables)) if group_tables is not None else None,
        share_strategy,
        m_active,
        salt,
        max_levels,
        frontier,
        max_matches,
        probes,
        kslot,
        donate,
        tuple(sorted(sub_bitmaps))
        if isinstance(sub_bitmaps, dict)
        else None,
        kg,
        tuple(sorted(sem_tables)) if sem_tables is not None else None,
        sem_topk,
        rule_progs,
    )
    return fn(
        shape_tables, nfa_tables, group_tables, client_hash, topic_hash,
        rand, sub_bitmaps, bytes_mat, lengths,
        sem_tables, q_vecs, rule_feats, rule_valid,
    )


def table_placement(mesh: Mesh):
    """Canonical placement for match tables: replicated over the mesh.
    Returned as a (name, np_array) -> device array fn so DeviceDeltaSync
    can upload straight into the sharded layout."""
    sh = NamedSharding(mesh, P())
    return lambda _name, arr: jax.device_put(arr, sh)


def bitmap_placement(mesh: Mesh):
    """Canonical placement for subscriber bitmaps: lanes sharded on 'tp'."""
    sh = NamedSharding(mesh, P(None, "tp"))
    return lambda _name, arr: jax.device_put(arr, sh)


def csr_placement(mesh: Mesh):
    """Canonical placement for the SPARSE subscriber table
    (ops/csr_table.py): every array's leading axis is the shard-owner
    axis (subscription owned by ``slot % shards``), sharded over 'tp' —
    the CSR twin of the dense lane sharding, O(subscriptions / tp)
    per device. Slot ids are stored globally, so per-shard compact
    lists concatenate over 'tp' with no lane rebase."""
    sh = NamedSharding(mesh, P("tp", None))
    return lambda _name, arr: jax.device_put(arr, sh)


def semantic_placement(mesh: Mesh):
    """Canonical placement for the semantic table
    (ops/semantic_table.py): every array's leading axis is the
    shard-owner axis (entry owned by ``slot % shards``), sharded over
    'tp' — the CSR slot-ownership regime, so per-shard semantic winners
    are GLOBAL slot ids and the compact rows concatenate over 'tp'
    with no lane rebase. O(filters / tp) embedding rows per device."""
    sh = NamedSharding(mesh, P("tp"))
    return lambda _name, arr: jax.device_put(arr, sh)


def retained_placement(mesh: Mesh):
    """Canonical placement for retained-topic chunks: ROWS sharded on
    'dp' (each dp slice scans its share of the stored topics; CHUNK is a
    pow2, so any pow2 dp divides it). Storm filter tables ride
    `table_placement` (replicated) like every other match table."""
    sh = NamedSharding(mesh, P("dp", None))
    return lambda _name, arr: jax.device_put(arr, sh)


def session_placement(mesh: Mesh):
    """Canonical placement for the session table (ops/session_table.py):
    1-D row/slot lanes sharded over 'dp' (pow2 capacities, so any pow2
    dp divides them) — each dp slice owns its share of the inflight
    rows, consistent with PR 10's shard-ownership regime. Delta scatters
    and compaction-offered buffers land pre-sharded through this hook;
    nothing is re-placed per batch."""
    sh = NamedSharding(mesh, P("dp"))
    return lambda _name, arr: jax.device_put(arr, sh)


def place_batch(mesh: Mesh, bytes_mat, lengths):
    """Canonical placement for a topic batch: rows sharded on 'dp'."""
    bm = jax.device_put(bytes_mat, NamedSharding(mesh, P("dp", None)))
    ln = jax.device_put(lengths, NamedSharding(mesh, P("dp")))
    return bm, ln


def shard_shape_inputs(
    mesh: Mesh,
    shape_tables: Dict,
    nfa_tables: Optional[Dict],
    sub_bitmaps,
    bytes_mat,
    lengths,
):
    """device_put the serving (shape) engine's inputs with the
    canonical shardings — built from the placement helpers above (the
    ONE place the layout is declared for every caller: dryrun, tests,
    DeviceRouter mesh mode)."""
    tp = table_placement(mesh)
    st = {k: tp(k, v) for k, v in shape_tables.items()}
    nt = (
        {k: tp(k, v) for k, v in nfa_tables.items()}
        if nfa_tables is not None
        else None
    )
    sb = bitmap_placement(mesh)("sub_bitmaps", sub_bitmaps)
    bm, ln = place_batch(mesh, bytes_mat, lengths)
    return st, nt, sb, bm, ln
