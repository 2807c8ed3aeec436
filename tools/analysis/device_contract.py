"""Tier B of the device-contract auditor: read the COMPILED artifact.

The AST checkers (SD/HT/RT) pin what the *source* may say; this module
pins what the *jaxpr* may contain. Every kernel registered through
`emqx_tpu.ops.contract.device_contract` is traced with `jax.make_jaxpr`
/ `jax.eval_shape` over a small config matrix (batch size, bitmap
width, Kslot, mesh shape) — abstract tracing on CPU, nothing executes —
and the trace is held against the declaration and a golden snapshot:

  * dtype discipline — forbidden dtypes (f64/i64 widenings by default)
    may appear nowhere: not as a `convert_element_type` target, not in
    any intermediate or output aval;
  * collective set — the union of collective primitives over the matrix
    must EQUAL the contract's declaration (a new `psum` is a new ICI
    dependency; a vanished one means the declaration rots);
  * readback bounds — declared outputs must stay under their byte
    bounds (`slots` is O(B*Kslot), never O(B*W));
  * trace stability — tracing the same config twice must produce an
    identical jaxpr, and distinct configs must produce exactly one
    program each (a retrace-regression gate);
  * golden snapshots — the normalized trace summary (primitive counts,
    collectives, output avals, digest) is diffed against
    `tests/fixtures/analysis/jaxprs/<kernel>.json`; refresh with
    `python -m tools.analysis --contracts --update-snapshots` after a
    DELIBERATE kernel change.

Configs that need more devices than the process has are skipped with a
note (the tier-1 suite provides the virtual 8-device CPU mesh).
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[2]
DEFAULT_SNAPSHOT_DIR = ROOT / "tests" / "fixtures" / "analysis" / "jaxprs"

COLLECTIVE_PRIMS = {
    "psum", "pmax", "pmin", "pmean", "all_gather", "all_to_all",
    "ppermute", "pshuffle", "psum_scatter", "axis_index",
}
# trace-level spellings -> the contract's canonical collective names.
# `pvary` is deliberately NOT a collective here: shard_map's varying-axes
# typing inserts it implicitly (hundreds per trace) and it lowers to a
# device-local no-op, so it is not a contractual ICI dependency the way
# a psum is.
CANON_PRIM = {
    "psum_invariant": "psum",
    "all_gather_invariant": "all_gather",
}


@dataclass
class AuditReport:
    problems: List[str] = field(default_factory=list)
    skipped: List[str] = field(default_factory=list)
    kernels: Dict[str, Dict] = field(default_factory=dict)
    updated: List[str] = field(default_factory=list)

    @property
    def clean(self) -> bool:
        return not self.problems

    def to_json(self) -> Dict:
        return {
            "clean": self.clean,
            "problems": self.problems,
            "skipped": self.skipped,
            "updated": self.updated,
            "kernels": self.kernels,
        }


def render_audit(doc: Dict) -> str:
    out = []
    for name, summary in sorted(doc.get("kernels", {}).items()):
        out.append(
            f"contract {name}: {len(summary)} config(s) traced"
        )
    for note in doc.get("skipped", []):
        out.append(f"contract skip: {note}")
    for name in doc.get("updated", []):
        out.append(f"contract snapshot updated: {name}")
    n = len(doc.get("problems", []))
    for p in doc.get("problems", []):
        out.append(f"contract VIOLATION: {p}")
    out.append(
        f"device-contract audit: {n} problem(s), "
        f"{len(doc.get('kernels', {}))} kernel(s)"
    )
    return "\n".join(out)


def _ensure_jax():
    """Import jax for ABSTRACT tracing: CPU platform, enough virtual
    devices for the mesh configs. Only effective before first import —
    inside the test suite the conftest already provides the 8-device
    CPU topology."""
    if "jax" not in sys.modules:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    import jax  # noqa: F401

    return jax


# -- jaxpr introspection ----------------------------------------------------

def _iter_jaxprs(jaxpr):
    """Yield a jaxpr and every sub-jaxpr reachable through eqn params."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for val in eqn.params.values():
            for sub in _as_jaxprs(val):
                yield from _iter_jaxprs(sub)


def _as_jaxprs(val):
    from jax.extend import core as jcore

    if isinstance(val, jcore.ClosedJaxpr):
        return [val.jaxpr]
    if isinstance(val, jcore.Jaxpr):
        return [val]
    if isinstance(val, (tuple, list)):
        out = []
        for v in val:
            out.extend(_as_jaxprs(v))
        return out
    return []


def _trace_summary(closed_jaxpr, out_shapes) -> Dict:
    """Normalize one trace into the snapshot form."""
    prims: Dict[str, int] = {}
    bad_dtypes: Dict[str, List[str]] = {}
    for j in _iter_jaxprs(closed_jaxpr.jaxpr):
        for eqn in j.eqns:
            pname = CANON_PRIM.get(eqn.primitive.name, eqn.primitive.name)
            prims[pname] = prims.get(pname, 0) + 1
            for var in list(eqn.outvars) + list(eqn.invars):
                aval = getattr(var, "aval", None)
                dt = getattr(aval, "dtype", None)
                if dt is not None:
                    bad_dtypes.setdefault(str(dt), []).append(
                        eqn.primitive.name
                    )
    collectives = sorted(set(prims) & COLLECTIVE_PRIMS)
    outputs = {}
    from jax.tree_util import tree_flatten_with_path

    leaves, _ = tree_flatten_with_path(out_shapes)
    for path, leaf in leaves:
        name = ".".join(_path_part(p) for p in path) or "out"
        outputs[name] = f"{leaf.dtype}[{','.join(map(str, leaf.shape))}]"
    import re

    # `lax.reduce(..., bitwise_or, ...)` prints its computation as
    # `<function bitwise_or at 0x7f...>` — strip the per-process address
    # (and any other embedded object id) or the digest is not portable
    text = re.sub(r" at 0x[0-9a-fA-F]+", "", str(closed_jaxpr))
    # multi-axis collective params print their axis names in SET order,
    # which follows the per-process string-hash seed — sort them
    # (jax 0.9's shard_map eqn also prints `manual_axes=frozenset({...})`)
    def _sorted_names(m):
        return m.group(1) + ", ".join(
            sorted(p.strip() for p in m.group(2).split(",") if p.strip())
        ) + m.group(3)

    text = re.sub(r"(axes=\()([^)]*)(\))", _sorted_names, text)
    text = re.sub(r"(frozenset\(\{)([^}]*)(\}\))", _sorted_names, text)
    return {
        "primitives": dict(sorted(prims.items())),
        "collectives": collectives,
        "outputs": dict(sorted(outputs.items())),
        "digest": hashlib.sha256(text.encode()).hexdigest()[:16],
        "_dtypes": sorted(bad_dtypes),  # all dtypes seen (for the check)
    }


def _path_part(p) -> str:
    for attr in ("key", "idx", "name"):
        if hasattr(p, attr):
            return str(getattr(p, attr))
    return str(p)


# -- kernel harnesses -------------------------------------------------------
# One tiny host-built workload (real table builders, so invariants like
# pow2 capacities hold) shared by every kernel; per-kernel closures bind
# the static args and name the outputs.

def _workload(max_subs: int = 512):
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    import __graft_entry__ as ge

    return ge._workload(max_subs=max_subs)


def _configs_single() -> List[Dict]:
    return [
        {"B": 8, "kslot": 0},
        {"B": 8, "kslot": 8},
        {"B": 16, "kslot": 8},
    ]


def _configs_mesh() -> List[Dict]:
    return [
        {"B": 8, "kslot": 0, "dp": 1, "tp": 1},
        {"B": 8, "kslot": 8, "dp": 2, "tp": 2},
    ]


def _cfg_key(cfg: Dict) -> str:
    parts = [f"B{cfg['B']}", f"k{cfg['kslot']}"]
    if "D" in cfg:
        parts.append(f"D{cfg['D']}")
    if "dp" in cfg:
        parts.append(f"dp{cfg['dp']}tp{cfg['tp']}")
    return "_".join(parts)


def _harness(name: str):
    """-> (configs, build(cfg) -> (traceable, args)) for a kernel, or
    None for registered kernels the audit has no recipe for."""
    import numpy as np

    if name == "segment_scatter_insert":
        # B = the pow2 delta bucket; two buckets pin the recompile story
        configs = [
            {"B": 16, "kslot": 0},
            {"B": 64, "kslot": 0},
        ]
    elif name == "session_ack_step":
        # B = the pow2 rider-write bucket; kslot doubles as sweep_k
        # (kslot=0: pure scatter ride, no sweep stage traces)
        configs = [
            {"B": 16, "kslot": 0},
            {"B": 16, "kslot": 8},
            {"B": 64, "kslot": 8},
        ]
    elif name == "compact_fanout_slots":
        # kslot=0 means "compaction off" — the stage never traces
        configs = [
            {"B": 8, "kslot": 8},
            {"B": 16, "kslot": 8},
            {"B": 8, "kslot": 32},
        ]
    elif name == "sparse_fanout_slots":
        # the CSR gather-union stage exists only with a positive cap
        configs = [
            {"B": 8, "kslot": 8},
            {"B": 16, "kslot": 8},
            {"B": 8, "kslot": 32},
        ]
    elif name == "semantic_match_step":
        # kslot doubles as topk; the matrix pins the embedding-dim axis
        # too (docs/semantic_routing.md)
        configs = [
            {"B": 8, "kslot": 4, "D": 16},
            {"B": 8, "kslot": 8, "D": 16},
            {"B": 8, "kslot": 4, "D": 32},
        ]
    elif name == "sem_dist_shape_step":
        # the serving builder traced WITH a semantic table (+ one
        # compiled rule predicate): 1x1 and 2x2 mesh rows
        configs = [
            {"B": 8, "kslot": 8, "D": 16, "dp": 1, "tp": 1},
            {"B": 8, "kslot": 8, "D": 16, "dp": 2, "tp": 2},
        ]
    elif name == "sparse_shape_route_step":
        # the serving jit traced against a CSR subscriber table
        configs = [
            {"B": 8, "kslot": 8},
            {"B": 16, "kslot": 8},
        ]
    elif name in ("shape_route_step", "fused_route_retained_step"):
        configs = _configs_single()
    elif name in (
        "dist_shape_step", "dist_fused_step", "sparse_dist_shape_step",
    ):
        configs = (
            [
                {"B": 8, "kslot": 8, "dp": 2, "tp": 2},
                {"B": 8, "kslot": 16, "dp": 2, "tp": 2},
            ]
            if name == "sparse_dist_shape_step"
            else _configs_mesh()
        )
    else:
        return None

    def build(cfg):
        from functools import partial

        index, subs, bytes_mat, lengths, m_active = _workload()
        B = cfg["B"]
        bytes_mat = bytes_mat[:B]
        lengths = np.asarray(lengths[:B])
        bits = subs.pack(index.num_filters_capacity)
        salt = index.salt
        kw = dict(max_levels=8, frontier=8, max_matches=8, probes=8)
        if name == "segment_scatter_insert":
            from emqx_tpu.ops.segments import segment_scatter_impl

            nb = cfg["B"]
            flats = {
                "shape_tab": np.full(4096, -1, np.int32),
                "sub_bitmaps": np.zeros(2048, np.uint32),
            }
            idxs = {
                k: np.arange(nb, dtype=np.int32) for k in flats
            }
            vals = {
                k: np.ones(nb, v.dtype) for k, v in flats.items()
            }
            return segment_scatter_impl, (flats, idxs, vals)
        if name == "session_ack_step":
            from emqx_tpu.ops.session_table import (
                ROW_LANES,
                SessionTable,
                session_ack_impl,
            )

            t = SessionTable(capacity=1024, slots=256)
            tables = {
                k: v.copy() for k, v in t.device_snapshot().items()
            }
            nb = cfg["B"]
            idxs = {k: np.arange(nb, dtype=np.int32) for k in ROW_LANES}
            vals = {k: np.ones(nb, np.int32) for k in ROW_LANES}
            clock = np.asarray([100, 300], np.int32)
            fn = partial(session_ack_impl, sweep_k=cfg["kslot"])
            return fn, (tables, idxs, vals, clock)
        if name == "compact_fanout_slots":
            from emqx_tpu.models.router_model import compact_fanout_slots

            W = bits.shape[1]
            bm = np.zeros((B, W), np.uint32)

            def fn(bm):
                slots, count, over = compact_fanout_slots(
                    bm, cfg["kslot"]
                )
                return {"slots": slots, "count": count, "overflow": over}

            return fn, (bm,)
        if name == "sparse_fanout_slots":
            from emqx_tpu.models.router_model import SubscriberTable
            from emqx_tpu.ops.csr_table import sparse_fanout_slots

            st = SubscriberTable(mode="sparse")
            for i in range(64):
                st.add(i % 16, i)
            csr = {
                k: v.copy() for k, v in st.device_snapshot().items()
            }
            matched = np.full((B, 8), -1, np.int32)
            matched[:, 0] = np.arange(B, dtype=np.int32) % 16

            def sfn(csr, matched):
                slots, count, over, live = sparse_fanout_slots(
                    csr, matched, kslot=cfg["kslot"]
                )
                return {
                    "slots": slots,
                    "count": count,
                    "overflow": over,
                    "live": live,
                }

            return sfn, (csr, matched)
        if name == "semantic_match_step":
            from emqx_tpu.ops.semantic_table import (
                SemanticTable,
                semantic_match_step,
            )

            sem = _sem_workload(cfg["D"], cfg["kslot"], shards=1)
            st_sem = {
                k: v.copy() for k, v in sem.device_snapshot().items()
            }
            matched = np.full((B, 8), -1, np.int32)
            matched[:, 0] = np.arange(B, dtype=np.int32) % 4
            qv = np.zeros((B, cfg["D"]), np.float32)

            def qfn(st_sem, qv, matched):
                sl, cnt = semantic_match_step(
                    st_sem, qv, matched, cfg["kslot"]
                )
                return {"sem_slots": sl, "sem_count": cnt}

            return qfn, (st_sem, qv, matched)
        if name == "sparse_shape_route_step":
            from emqx_tpu.models.router_model import shape_route_step

            subs.set_mode("sparse")
            subs.pack(index.num_filters_capacity)
            csr = {
                k: v.copy() for k, v in subs.device_snapshot().items()
            }
            with_nfa = index.residual_count > 0
            fn = partial(
                shape_route_step,
                m_active=m_active,
                with_nfa=with_nfa,
                salt=salt,
                kslot=cfg["kslot"],
                **kw,
            )
            nfa = index.nfa.device_snapshot() if with_nfa else None
            return fn, (
                index.shapes.device_snapshot(), nfa, csr,
                bytes_mat, lengths,
            )
        if name == "shape_route_step":
            from emqx_tpu.models.router_model import shape_route_step

            with_nfa = index.residual_count > 0
            fn = partial(
                shape_route_step,
                m_active=m_active,
                with_nfa=with_nfa,
                salt=salt,
                kslot=cfg["kslot"],
                **kw,
            )
            nfa = index.nfa.device_snapshot() if with_nfa else None
            return fn, (
                index.shapes.device_snapshot(), nfa, bits,
                bytes_mat, lengths,
            )
        if name == "fused_route_retained_step":
            from emqx_tpu.models.router_model import (
                fused_route_retained_step,
            )
            from emqx_tpu.ops.route_index import RouteIndex

            with_nfa = index.residual_count > 0
            nfa = index.nfa.device_snapshot() if with_nfa else None
            # retained half: a small deterministic storm-filter table +
            # one (scaled-down) topic chunk — abstract tracing only, so
            # the real 1M-row CHUNK is unnecessary
            ridx = RouteIndex()
            for f in ("site/+/a", "site/#"):
                ridx.add(f)
            rst = ridx.shapes.device_snapshot()
            r_with_nfa = ridx.residual_count > 0
            rnt = ridx.nfa.device_snapshot() if r_with_nfa else None
            ret_bytes = np.zeros((64, 16), np.uint8)
            fn = partial(
                fused_route_retained_step,
                m_active=m_active,
                with_nfa=with_nfa,
                salt=salt,
                ret_m_active=ridx.shapes.m_active(floor=1),
                ret_with_nfa=r_with_nfa,
                ret_salt=ridx.salt,
                ret_max_levels=8,
                ret_narrow=True,
                kslot=cfg["kslot"],
                **kw,
            )
            return fn, (
                index.shapes.device_snapshot(), nfa, bits,
                bytes_mat, lengths, rst, rnt, ret_bytes,
            )
        # mesh builders
        import jax

        from emqx_tpu.parallel.mesh import make_mesh

        n = cfg["dp"] * cfg["tp"]
        if len(jax.devices()) < n:
            raise _SkipConfig(
                f"{name} {_cfg_key(cfg)}: needs {n} devices, have "
                f"{len(jax.devices())}"
            )
        mesh = make_mesh(n, tp=cfg["tp"])
        # batch divisible by dp, lanes by tp
        if B % cfg["dp"]:
            raise _SkipConfig(f"{name}: B={B} not divisible by dp")
        if name == "dist_fused_step":
            from emqx_tpu.ops.route_index import RouteIndex
            from emqx_tpu.parallel.mesh import _dist_fused_step_fn

            with_nfa = index.residual_count > 0
            st = index.shapes.device_snapshot()
            nt = index.nfa.device_snapshot() if with_nfa else None
            # retained half: small storm-filter table + a dp-divisible
            # topic-chunk slab (abstract tracing — no 1M-row CHUNK)
            ridx = RouteIndex()
            for f in ("site/+/a", "site/#"):
                ridx.add(f)
            rst = ridx.shapes.device_snapshot()
            r_with_nfa = ridx.residual_count > 0
            rnt = ridx.nfa.device_snapshot() if r_with_nfa else None
            ret_bytes = np.zeros((64, 16), np.uint8)
            fn = _dist_fused_step_fn(
                mesh,
                tuple(sorted(st)),
                tuple(sorted(nt)) if nt is not None else None,
                None,  # group_keys
                tuple(sorted(rst)),
                tuple(sorted(rnt)) if rnt is not None else None,
                0,  # share_strategy
                m_active,
                salt,
                kw["max_levels"],
                kw["frontier"],
                kw["max_matches"],
                kw["probes"],
                cfg["kslot"],
                ridx.shapes.m_active(floor=1),
                r_with_nfa,
                ridx.salt,
                8,  # ret_max_levels
                True,  # ret_narrow
            )
            return fn, (st, nt, None, None, None, None, bits, bytes_mat,
                        lengths, rst, rnt, ret_bytes,
                        None, None, None, None)
        from emqx_tpu.parallel.mesh import _dist_shape_step_fn

        with_nfa = index.residual_count > 0
        st = index.shapes.device_snapshot()
        nt = index.nfa.device_snapshot() if with_nfa else None
        if name == "sem_dist_shape_step":
            sem = _sem_workload(cfg["D"], cfg["kslot"], shards=cfg["tp"])
            st_sem = {
                k: v.copy() for k, v in sem.device_snapshot().items()
            }
            qv = np.zeros((B, cfg["D"]), np.float32)
            # one compiled WHERE predicate rides the same golden: the
            # in-launch rule-mask stage is pinned here too
            prog = (("feat", 0), ("lit", 1.0), ("ge",))
            rfeats = np.zeros((B, 1), np.float32)
            rvalid = np.ones((B, 1), bool)
            fn = _dist_shape_step_fn(
                mesh,
                tuple(sorted(st)),
                tuple(sorted(nt)) if nt is not None else None,
                None,  # group_keys
                0,  # share_strategy
                m_active,
                salt,
                kw["max_levels"],
                kw["frontier"],
                kw["max_matches"],
                kw["probes"],
                cfg["kslot"],
                False,  # donate
                None,  # sub_keys (dense fan-out)
                0,  # kg
                tuple(sorted(st_sem)),
                cfg["kslot"],  # sem_topk
                (prog,),
            )
            return fn, (st, nt, None, None, None, None, bits, bytes_mat,
                        lengths, st_sem, qv, rfeats, rvalid)
        if name == "sparse_dist_shape_step":
            subs.set_mode("sparse")
            subs.set_shards(cfg["tp"])
            subs.pack(index.num_filters_capacity)
            csr = {
                k: v.copy() for k, v in subs.device_snapshot().items()
            }
            fn = _dist_shape_step_fn(
                mesh,
                tuple(sorted(st)),
                tuple(sorted(nt)) if nt is not None else None,
                None,  # group_keys
                0,  # share_strategy
                m_active,
                salt,
                kw["max_levels"],
                kw["frontier"],
                kw["max_matches"],
                kw["probes"],
                cfg["kslot"],
                False,  # donate
                tuple(sorted(csr)),
                0,  # kg (auto: 2 x kslot)
            )
            return fn, (st, nt, None, None, None, None, csr, bytes_mat,
                        lengths, None, None, None, None)
        fn = _dist_shape_step_fn(
            mesh,
            tuple(sorted(st)),
            tuple(sorted(nt)) if nt is not None else None,
            None,  # group_keys
            0,  # share_strategy
            m_active,
            salt,
            kw["max_levels"],
            kw["frontier"],
            kw["max_matches"],
            kw["probes"],
            cfg["kslot"],
        )
        return fn, (st, nt, None, None, None, None, bits, bytes_mat,
                    lengths, None, None, None, None)

    return configs, build


def _sem_workload(dim: int, topk: int, shards: int = 1):
    """Deterministic SemanticTable: scoped + unscoped + a tombstone."""
    import numpy as np

    from emqx_tpu.ops.semantic_table import SemanticTable

    sem = SemanticTable(dim=dim, topk=topk, shards=shards)
    rng = np.random.default_rng(0x5E)
    for i in range(12):
        sem.add(
            64 + i, rng.normal(size=dim), 0.4,
            fid=-1 if i % 3 == 0 else i % 4,
        )
    sem.remove(64 + 5)  # a tombstone lane in the golden
    return sem


class _SkipConfig(Exception):
    pass


# -- the audit --------------------------------------------------------------

def run_audit(
    update_snapshots: bool = False,
    snapshot_dir: Optional[Path] = None,
    registry: Optional[Dict] = None,
    harness=None,
) -> AuditReport:
    """Trace every registered kernel and hold it to its contract.

    `registry`/`harness` are injectable for the fixture-kernel tests;
    the default is the product registry (importing the kernel modules
    populates it) and `_harness`.
    """
    jax = _ensure_jax()
    snapshot_dir = Path(snapshot_dir or DEFAULT_SNAPSHOT_DIR)
    harness = harness or _harness
    report = AuditReport()

    if registry is None:
        # importing the kernel modules populates the registry
        import emqx_tpu.models.router_model  # noqa: F401
        import emqx_tpu.ops.session_table  # noqa: F401
        import emqx_tpu.parallel.mesh  # noqa: F401
        from emqx_tpu.ops.contract import REGISTRY

        registry = REGISTRY

    for name, contract in sorted(registry.items()):
        recipe = harness(name)
        if recipe is None:
            report.problems.append(
                f"{name}: registered but the audit has no harness for it"
            )
            continue
        configs, build = recipe
        traced: Dict[str, Dict] = {}
        for cfg in configs:
            key = _cfg_key(cfg)
            try:
                fn, args = build(dict(cfg))
            except _SkipConfig as e:
                report.skipped.append(str(e))
                continue
            jaxpr1 = jax.make_jaxpr(fn)(*args)
            jaxpr2 = jax.make_jaxpr(fn)(*args)
            shapes = jax.eval_shape(fn, *args)
            summary = _trace_summary(jaxpr1, shapes)
            if str(jaxpr1) != str(jaxpr2):
                report.problems.append(
                    f"{name} {key}: tracing twice produced different "
                    "jaxprs (nondeterministic trace)"
                )
            self_check(report, name, key, cfg, contract, summary)
            traced[key] = summary
        if not traced:
            continue
        # collective declaration must match the union over the matrix
        union = sorted(
            {c for s in traced.values() for c in s["collectives"]}
        )
        declared = sorted(contract.collectives)
        if union != declared:
            report.problems.append(
                f"{name}: collective set over the matrix is {union}, "
                f"contract declares {declared} — the declaration must "
                "match exactly"
            )
        digests = {s["digest"] for s in traced.values()}
        if len(digests) != len(traced):
            report.problems.append(
                f"{name}: {len(traced)} configs produced "
                f"{len(digests)} distinct programs — two configs "
                "compiled to the same trace (dead config knob?) "
            )
        # snapshot diff
        public = {
            k: {kk: vv for kk, vv in s.items() if not kk.startswith("_")}
            for k, s in traced.items()
        }
        snap_path = snapshot_dir / f"{name}.json"
        if update_snapshots:
            snapshot_dir.mkdir(parents=True, exist_ok=True)
            snap_path.write_text(json.dumps(public, indent=2) + "\n")
            report.updated.append(name)
        elif not snap_path.exists():
            report.problems.append(
                f"{name}: no golden snapshot at {snap_path}; run "
                "`python -m tools.analysis --contracts "
                "--update-snapshots`"
            )
        else:
            golden = json.loads(snap_path.read_text())
            for key, summary in public.items():
                if key not in golden:
                    report.problems.append(
                        f"{name} {key}: config missing from snapshot — "
                        "refresh with --update-snapshots"
                    )
                    continue
                diffs = _diff_summary(golden[key], summary)
                for d in diffs:
                    report.problems.append(f"{name} {key}: {d}")
        report.kernels[name] = public
    return report


def self_check(report, name, key, cfg, contract, summary) -> None:
    """Per-config declaration checks (dtypes, collectives, bounds)."""
    for dt in summary["_dtypes"]:
        if dt in contract.forbid_dtypes:
            report.problems.append(
                f"{name} {key}: forbidden dtype {dt} appears in the "
                "trace (widening breaks the readback/HBM budget)"
            )
    extra = set(summary["collectives"]) - set(contract.collectives)
    if extra:
        report.problems.append(
            f"{name} {key}: undeclared collective(s) {sorted(extra)} "
            f"(contract allows {sorted(contract.collectives)})"
        )
    for out_name, bound in contract.out_bounds.items():
        spec = summary["outputs"].get(out_name)
        if spec is None:
            continue  # output not present in this config (e.g. kslot=0)
        limit = bound(cfg)
        nbytes = _spec_nbytes(spec)
        if nbytes > limit:
            report.problems.append(
                f"{name} {key}: output {out_name} is {spec} "
                f"({nbytes}B) > contract bound {limit}B — the compact "
                "output scaled with the wrong dimension"
            )


def _spec_nbytes(spec: str) -> int:
    import numpy as np

    dtype, _, dims = spec.partition("[")
    shape = [int(d) for d in dims.rstrip("]").split(",") if d]
    n = 1
    for d in shape:
        n *= d
    return n * np.dtype(dtype).itemsize


def _diff_summary(golden: Dict, current: Dict) -> List[str]:
    out = []
    if golden.get("digest") != current.get("digest"):
        out.append(
            f"jaxpr digest {current.get('digest')} != golden "
            f"{golden.get('digest')} (kernel trace changed; if "
            "deliberate, refresh with --update-snapshots)"
        )
    if golden.get("collectives") != current.get("collectives"):
        out.append(
            f"collectives {current.get('collectives')} != golden "
            f"{golden.get('collectives')}"
        )
    if golden.get("outputs") != current.get("outputs"):
        out.append(
            f"outputs {current.get('outputs')} != golden "
            f"{golden.get('outputs')}"
        )
    gp, cp = golden.get("primitives", {}), current.get("primitives", {})
    if gp != cp:
        changed = sorted(
            k for k in set(gp) | set(cp) if gp.get(k) != cp.get(k)
        )
        out.append(
            "primitive counts changed: "
            + ", ".join(
                f"{k} {gp.get(k, 0)}->{cp.get(k, 0)}" for k in changed[:8]
            )
        )
    return out


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        prog="python -m tools.analysis.device_contract",
        description="jaxpr-level device-contract audit",
    )
    p.add_argument("--update-snapshots", action="store_true")
    p.add_argument("--format", choices=("text", "json"), default="text")
    args = p.parse_args(argv)
    report = run_audit(update_snapshots=args.update_snapshots)
    if args.format == "json":
        print(json.dumps(report.to_json(), indent=2))
    else:
        print(render_audit(report.to_json()))
    return 0 if report.clean else 1


if __name__ == "__main__":
    sys.exit(main())
