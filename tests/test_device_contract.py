"""Tier B of the device-contract auditor: jaxpr audit + golden
snapshots (tools/analysis/device_contract).

The positive gate traces every registered production kernel
(shape_route_step, compact_fanout_slots, the mesh step builders) over the
config matrix and holds them to their declared contracts AND the
checked-in snapshots under tests/fixtures/analysis/jaxprs/. The negative
tests prove the audit actually bites: a seeded dtype mutation in a
fixture kernel must fail, and the --update-snapshots workflow must
recover a clean run.
"""

import ast
import functools
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from tools.analysis.device_contract import (  # noqa: E402
    DEFAULT_SNAPSHOT_DIR,
    run_audit,
)

jax = pytest.importorskip("jax")


# -- the production-kernel gate ---------------------------------------------

def test_registered_kernels_pass_against_checked_in_snapshots():
    report = run_audit()
    assert report.clean, "\n".join(report.problems)
    # the registry really covered the serving kernels, and the source
    # scan the reachability cases below run on sees the same set
    assert {"shape_route_step", "compact_fanout_slots"} <= set(
        report.kernels
    )
    assert set(report.kernels) == set(_REGISTRATIONS)
    for name, configs in report.kernels.items():
        assert configs, name


def test_mesh_builders_are_audited_on_the_virtual_mesh():
    if len(jax.devices()) < 4:
        pytest.skip("needs the 8-device CPU topology from conftest")
    report = run_audit()
    assert "dist_shape_step" in report.kernels
    # the scale-out serving engine's fused builder is under audit too
    assert "dist_fused_step" in report.kernels
    # the declared collective contract was exercised, not vacuous
    for builder in ("dist_shape_step", "dist_fused_step"):
        k8 = [
            s for key, s in report.kernels[builder].items()
            if "k8" in key
        ]
        assert k8 and any(
            "axis_index" in s["collectives"] for s in k8
        ), builder
        assert all(
            "psum" in s["collectives"]
            for s in report.kernels[builder].values()
        ), builder


def test_compact_outputs_stay_o_b_kslot():
    report = run_audit()
    for key, summary in report.kernels["compact_fanout_slots"].items():
        b, k = key.split("_")
        B, K = int(b[1:]), int(k[1:])
        spec = summary["outputs"]["slots"]
        dims = [int(d) for d in spec.split("[")[1].rstrip("]").split(",")]
        assert dims == [B, K], (key, spec)  # never [B, W*32]


# -- every registered kernel is reachable from product code ------------------
#
# A contract kernel that no module under emqx_tpu/ references, other than
# its own registration and the jit-cache trimmer, is compiled, snapshotted
# and kept alive for nobody (ROADMAP aim 3). The scan is plain AST: no
# import, so a registration costs nothing to find.

_PKG = ROOT / "emqx_tpu"
_TRIMMER = "_trim_jit_cache"


def _is_registration(call) -> bool:
    """`device_contract("name", ...)` (the inner call of a registration)."""
    return (
        isinstance(call, ast.Call)
        and isinstance(call.func, ast.Name)
        and call.func.id == "device_contract"
        and call.args
        and isinstance(call.args[0], ast.Constant)
    )


@functools.cache
def _tree(path):
    return ast.parse(path.read_text())


def _scan_registrations():
    """-> {kernel name: (handles, statement)}.

    `handles` are the names product code would reference to launch the
    kernel: the decorated def, the assigned name, and, where the
    registered object is an existing one passed by name, that name."""
    regs = {}
    for path in sorted(_PKG.rglob("*.py")):
        if "device_contract(" not in path.read_text():
            continue
        for node in ast.walk(_tree(path)):
            if isinstance(node, ast.FunctionDef):
                for dec in node.decorator_list:
                    if _is_registration(dec):
                        regs[dec.args[0].value] = ({node.name}, node)
                continue
            if not isinstance(node, (ast.Assign, ast.Expr)):
                continue
            outer = node.value
            if not (
                isinstance(outer, ast.Call) and _is_registration(outer.func)
            ):
                continue
            handles = set()
            if isinstance(node, ast.Assign):
                handles |= {
                    t.id for t in node.targets if isinstance(t, ast.Name)
                }
            if outer.args and isinstance(outer.args[0], ast.Name):
                handles.add(outer.args[0].id)
            regs[outer.func.args[0].value] = (handles, node)
    return regs


_REGISTRATIONS = _scan_registrations()
# a registration statement is not a caller: not the kernel's own, and not
# another entry that registers the same object a second time. A decorated
# def is not in this set: its body is product code (and `_references`
# leaves a handle's own def out by name).
_REGISTRATION_STMTS = {
    id(stmt) for _handles, stmt in _REGISTRATIONS.values()
    if not isinstance(stmt, ast.FunctionDef)
}


def _references(tree, handles):
    """Line numbers in `tree` that name one of `handles`, outside the
    registration statements, any def of a handle and the trimmer."""
    hits = []

    def walk(node):
        if id(node) in _REGISTRATION_STMTS:
            return
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and (
            node.name == _TRIMMER or node.name in handles
        ):
            return
        if isinstance(node, ast.Name) and node.id in handles:
            hits.append(node.lineno)
        elif isinstance(node, ast.Attribute) and node.attr in handles:
            hits.append(node.lineno)
        elif isinstance(node, ast.ImportFrom):
            hits.extend(
                node.lineno for a in node.names if a.name in handles
            )
        for child in ast.iter_child_nodes(node):
            walk(child)

    walk(tree)
    return hits


@pytest.mark.parametrize("kernel", sorted(_REGISTRATIONS))
def test_registered_kernel_is_referenced_by_product_code(kernel):
    handles, _stmt = _REGISTRATIONS[kernel]
    assert handles, kernel
    refs = {
        str(path.relative_to(ROOT)): lines
        for path in sorted(_PKG.rglob("*.py"))
        if (lines := _references(_tree(path), handles))
    }
    assert refs, (
        f"contract kernel {kernel!r} ({sorted(handles)}) is referenced "
        f"under emqx_tpu/ only by its registration and {_TRIMMER}: "
        "delete it with its golden jaxpr, or call it"
    )


# -- fixture-kernel harness (for the negative tests) ------------------------

def _harness_for(fn):
    def harness(name):
        from functools import partial

        configs = [{"B": 4, "kslot": 4}]

        def build(cfg):
            x = np.zeros((cfg["B"], 8), np.int32)
            return partial(fn, kslot=cfg["kslot"]), (x,)

        return configs, build

    return harness


def _fixture_mod():
    import importlib.util

    path = ROOT / "tests" / "fixtures" / "analysis" / "contract_kernels.py"
    spec = importlib.util.spec_from_file_location("contract_kernels", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_snapshot_workflow_and_seeded_mutation(tmp_path):
    fx = _fixture_mod()

    # 1. no snapshot yet: the audit refuses, pointing at the workflow
    r = run_audit(
        registry=fx.REG_GOOD, harness=_harness_for(fx.good_kernel),
        snapshot_dir=tmp_path,
    )
    assert not r.clean
    assert any("--update-snapshots" in p for p in r.problems)

    # 2. refresh, then a clean rerun must pass
    r = run_audit(
        registry=fx.REG_GOOD, harness=_harness_for(fx.good_kernel),
        snapshot_dir=tmp_path, update_snapshots=True,
    )
    assert r.updated == ["fx_kernel"]
    r = run_audit(
        registry=fx.REG_GOOD, harness=_harness_for(fx.good_kernel),
        snapshot_dir=tmp_path,
    )
    assert r.clean, r.problems

    # 3. the seeded mutation (a forbidden float32 widening on the same
    # contract) must fail BOTH ways: the declaration check and the
    # golden-snapshot diff
    r = run_audit(
        registry=fx.REG_MUTATED, harness=_harness_for(fx.mutated_kernel),
        snapshot_dir=tmp_path,
    )
    assert not r.clean
    assert any("forbidden dtype float32" in p for p in r.problems), (
        r.problems
    )
    assert any("digest" in p for p in r.problems), r.problems

    # 4. and --update-snapshots is NOT a silent escape hatch for a
    # contract violation: the declaration check still fails
    r = run_audit(
        registry=fx.REG_MUTATED, harness=_harness_for(fx.mutated_kernel),
        snapshot_dir=tmp_path, update_snapshots=True,
    )
    assert any("forbidden dtype float32" in p for p in r.problems)


def test_checked_in_snapshots_exist_for_every_registered_kernel():
    import emqx_tpu.models.router_model  # noqa: F401
    import emqx_tpu.parallel.mesh  # noqa: F401
    from emqx_tpu.ops.contract import REGISTRY

    for name in REGISTRY:
        assert (DEFAULT_SNAPSHOT_DIR / f"{name}.json").exists(), name
