"""tpu_lint (tools/analysis): fixture-driven checker tests + the tier-1
run-on-repo gate.

The repo gate is the contract from the static-analysis PR: `emqx_tpu/`
stays clean of non-baseline findings — deleting a `with self._lock:`
around a guarded attribute, adding `time.sleep` to an `async def`,
typo'ing a config field or metric series name all fail this test.
"""

import json
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from tools.analysis import Baseline, run_analysis  # noqa: E402

FIXTURES = ROOT / "tests" / "fixtures" / "analysis"


def codes_by_file(report):
    out = {}
    for f in report.findings:
        out.setdefault(Path(f.path).name, set()).add(f.code)
    return out


def run_fixtures(checks):
    return run_analysis(FIXTURES, checks=checks)


# -- lock discipline --------------------------------------------------------

def test_lock_checker_flags_unlocked_access():
    report = run_fixtures(["lock"])
    by_file = codes_by_file(report)
    assert "LK001" in by_file.get("lock_bad.py", set())
    assert "LK002" in by_file.get("lock_bad.py", set())
    bad = [
        f for f in report.findings
        if f.path.endswith("lock_bad.py") and f.code == "LK001"
    ]
    # bump, read, locked_then_not, RegistryStyle.put, WrongLock.oops
    assert len(bad) == 5, [f.render() for f in bad]
    assert {f.symbol for f in bad} == {
        "Counter.bump", "Counter.read", "Counter.locked_then_not",
        "RegistryStyle.put", "WrongLock.oops",
    }


def test_lock_checker_accepts_compliant_and_annotated():
    report = run_fixtures(["lock"])
    good = [f for f in report.findings if f.path.endswith("lock_good.py")]
    assert not good, [f.render() for f in good]
    # the inline `# lint: disable=LK001` in lock_good.py was counted
    assert report.suppressed >= 1


# -- async blocking ---------------------------------------------------------

def test_async_checker_flags_blocking_calls():
    report = run_fixtures(["async"])
    bad = {
        (f.code, f.symbol)
        for f in report.findings
        if f.path.endswith("async_bad.py")
    }
    assert ("AB001", "sleepy") in bad
    assert ("AB001", "sleepy_from_import") in bad  # from-import alias
    assert ("AB002", "fetch") in bad
    assert ("AB002", "resolve") in bad
    assert ("AB003", "slurp") in bad
    assert ("AB004", "shell") in bad
    assert ("AB004", "sysexec") in bad
    assert ("AB005", "block_on") in bad


def test_async_checker_accepts_executor_and_sync_code():
    report = run_fixtures(["async"])
    good = [f for f in report.findings if f.path.endswith("async_good.py")]
    assert not good, [f.render() for f in good]


# -- jit purity -------------------------------------------------------------

def test_jit_checker_flags_reachable_impurities():
    report = run_fixtures(["jit"])
    bad = {
        (f.code, f.symbol)
        for f in report.findings
        if f.path.endswith("jit_bad.py")
    }
    assert ("JP001", "helper_sync") in bad
    assert ("JP002", "helper_cast") in bad
    assert ("JP003", "helper_mutates") in bad
    assert ("JP004", "helper_clock") in bad
    assert ("JP005", "helper_branches") in bad
    # reachable because it is passed BY NAME to lax.scan inside a root
    assert ("JP003", "scan_body") in bad


def test_jit_checker_ignores_host_side_code():
    report = run_fixtures(["jit"])
    good = [f for f in report.findings if f.path.endswith("jit_good.py")]
    assert not good, [f.render() for f in good]


# -- config keys ------------------------------------------------------------

def test_config_checker_flags_drift_and_dead_keys():
    report = run_fixtures(["config"])
    bad = {
        (f.code, f.detail)
        for f in report.findings
        if f.path.endswith("config_fixture.py")
    }
    assert ("CK001", "RouterConfig.min_btach") in bad
    assert ("CK001", "RouterConfig.enable_gpu") in bad  # via self.config
    assert ("CK002", "prot") in bad
    assert ("CK003", "never_read_anywhere") in bad
    # compliant reads (fields, methods, declared opt keys) stay silent
    details = {d for _, d in bad}
    assert "RouterConfig.enable_tpu" not in details
    assert "RouterConfig.effective_batch" not in details
    assert "bind" not in details


# -- metric names -----------------------------------------------------------

def test_metric_checker_flags_undeclared_series():
    report = run_fixtures(["metrics"])
    bad = {
        f.detail for f in report.findings
        if f.path.endswith("metrics_fixture.py")
    }
    assert bad == {
        "messages.recieved", "sessions.active", "dispatch.readback.bytez",
        "trace.spans.samplid", "device.compile.cout",
        "router.sync.skiped", "ingest.device.idle.secondz",
        "retained.storm.fuzed", "olp.lag_mz", "olp.tripz",
        "router.segment.hot.fil", "router.compact.runz",
        "router.sparse.overflow.rowz", "router.sparse.bytez",
        "racetrack.eventz", "race.reportz",
        "mesh.shard.fil", "mesh.shard.rebalanse",
        "mesh.shard.scatter.launchez",
        "session.store.inflite", "session.ack.ridez",
        "session.sweep.dew", "session.redeliveriez",
        "fabric.slab.pub.recordz", "ingest.zerocopy.recordz",
        "dispatch.serialize.framez",
        "semantic.filterz", "semantic.hitz",
        "rules.matchd", "rules.device.batchez",
        "slo.window_uz", "slo.ladder.wrung", "slo.violationz",
        "ingest.lane.depth.contrl", "ingest.lane.settle.secondz.control",
        "retained.storm.deferd",
        "profile.stage.queue_wate.seconds", "profile.capturez",
        "provenance.proxi", "device.kernel.shape_root_step.seconds",
        "replay.capturez", "analysis.replay.runz",
        "analysis.wirecompat.failurez", "proto.registry.formatz",
    }


# -- fault contracts --------------------------------------------------------

def test_fault_checker_flags_site_drift_and_undeclared_series():
    report = run_fixtures(["fault"])
    bad = {(f.code, f.detail) for f in report.findings}
    # injector-only site: config validation can never arm it
    assert ("FT001", "matcher.mystery") in bad
    # schema ghost: a rule naming it never fires
    assert ("FT001", "cluster.ghost") in bad
    # undeclared series at a metric call site and via a *_series kwarg
    assert ("FT002", "degrade.trips.devize") in bad
    assert ("FT002", "faults.injektd") in bad
    assert ("FT002", "degrade.state.devize") in bad
    # lockstep sites + declared series stay silent
    details = {d for _, d in bad}
    assert "device.launch" not in details
    assert "ingest.enqueue" not in details
    assert "degrade.state.device" not in details
    assert "degrade.probe.ok" not in details
    assert "faults.injected" not in details


def test_fault_checker_repo_registries_in_lockstep():
    # the live cross-check the checker exists for: emqx_tpu's injector
    # SITES and config FAULT_SITES agree, and every degrade.*/faults.*
    # series the degradation ladder emits is declared
    report = run_analysis(ROOT / "emqx_tpu", checks=["fault"])
    assert report.clean, "\n".join(f.render() for f in report.findings)


# -- sharding discipline ----------------------------------------------------

def test_shard_checker_flags_unbound_axes_and_stray_collectives():
    report = run_fixtures(["shard"])
    bad = {
        (f.code, f.symbol)
        for f in report.findings
        if f.path.endswith("sd_bad.py")
    }
    assert ("SD001", "bad_axis_body") in bad  # psum over 'rows'
    assert ("SD002", "stray_collective") in bad  # never shard_map-ped
    assert ("SD003", "bad_spec") in bad  # P('lanes')
    # the scale-out serving placements: a spec naming an unbound axis
    # in a mesh-serving-shaped helper is a pinned finding
    assert ("SD003", "bad_mesh_serving_placement") in bad  # P('dq')


def test_shard_checker_accepts_mesh_bound_and_reached_code():
    report = run_fixtures(["shard"])
    good = [f for f in report.findings if f.path.endswith("sd_good.py")]
    # psum('dp'), pmax('tp') via a helper, a non-literal axis parameter:
    # all legal (the helper and dynamic_axis are reached from step_body)
    assert not good, [f.render() for f in good]


# -- host-transfer discipline -----------------------------------------------

def test_transfer_checker_flags_unannotated_readbacks():
    report = run_fixtures(["transfer"])
    bad = {
        (f.code, f.symbol)
        for f in report.findings
        if f.path.endswith("ht_bad.py")
    }
    assert ("HT001", "direct_pull") in bad  # np.asarray(jit result)
    assert ("HT001", "scalar_pull") in bad  # float(device value)
    assert ("HT001", "sync_pull") in bad  # .block_until_ready()
    assert ("HT001", "_helper") in bad  # taint via the call site
    assert ("HT001", "via_return") in bad  # taint via return value
    assert ("HT002", "stale_annotation") in bad  # annotation, no transfer


def test_transfer_checker_accepts_annotated_and_host_code():
    report = run_fixtures(["transfer"])
    good = [f for f in report.findings if f.path.endswith("ht_good.py")]
    assert not good, [f.render() for f in good]


def test_multiline_statement_suppression():
    # the `# lint: disable=HT001` in ht_good.suppressed_site sits on the
    # CLOSING line of a multi-line call; the finding is reported at the
    # first line — span-aware suppression must connect the two
    report = run_fixtures(["transfer"])
    assert report.suppressed >= 1


# -- retrace hazards --------------------------------------------------------

def test_retrace_checker_flags_traced_shape_args():
    report = run_fixtures(["retrace"])
    bad = {
        (f.code, f.symbol, f.detail)
        for f in report.findings
        if f.path.endswith("rt_bad.py")
    }
    assert ("RT001", "leaky", "n") in bad  # jnp.zeros(traced)
    assert ("RT001", "wrong_static", "width") in bad  # .reshape(traced)
    assert ("RT001", "_fill", "m") in bad  # hazard through a helper
    assert ("RT001", "wrapped_impl", "n") in bad  # assignment-form jit


def test_retrace_checker_accepts_static_and_shape_derived():
    report = run_fixtures(["retrace"])
    good = [f for f in report.findings if f.path.endswith("rt_good.py")]
    assert not good, [f.render() for f in good]


# -- folded from tests/test_metric_names.py (wrapper deleted) ---------------

def test_metric_checker_sees_the_hot_path_call_sites():
    # the lint is only as good as its scan: it must actually see the
    # flight-recorder call sites it exists to guard
    from tools.analysis.checkers.metric_names import call_sites
    from tools.analysis.core import parse_modules

    names = set()
    for mod in parse_modules(ROOT / "emqx_tpu"):
        if mod.tree is None:
            continue
        names.update(name for _, name in call_sites(mod))
    for expected in (
        "ingest.batch.size",
        "router.device.seconds",
        "dispatch.fanout",
        "messages.routed.device",
        "dispatch.readback.bytes",
    ):
        assert expected in names, expected


# -- cross-context escapes --------------------------------------------------

def test_cx_checker_flags_cross_context_mutations():
    report = run_fixtures(["cx"])
    bad = {
        (f.code, f.symbol, f.detail)
        for f in report.findings
        if f.path.endswith("cx_bad.py")
    }
    # two writer contexts (loop + pool)
    assert ("CX001", "SharedState.cx_bump", "counter") in bad
    # written on the loop, read from the pool
    assert ("CX001", "SharedState.tick", "flights") in bad
    # raw threading.Thread(target=...) root
    assert ("CX001", "ThreadShared.cx_reader_loop", "tally") in bad
    # stale single-writer: a pool method writes the loop-declared field
    assert ("CX002", "SharedState.cx_bump", "stamp->loop") in bad
    # single-writer naming a context no root creates
    assert ("CX002", "SharedState", "mode->warp-core") in bad
    assert len(bad) == 5, sorted(bad)


def test_cx_checker_accepts_guarded_single_writer_and_waived():
    report = run_fixtures(["cx"])
    good = [f for f in report.findings if f.path.endswith("cx_good.py")]
    # GUARDED_BY attr, a correct `# single-writer: loop`, and the
    # inline-waived tombstone flag all stay silent
    assert not good, [f.render() for f in good]
    assert report.suppressed >= 1  # the WaivedShared waiver was counted


def test_cx_repo_runs_clean():
    # the rig the segmented-table refactor will be developed under:
    # every cross-context mutable field in emqx_tpu/ is locked, declared
    # single-writer, or explicitly waived — non-baseline zero
    report = run_analysis(ROOT / "emqx_tpu", checks=["cx"])
    assert report.clean, "\n".join(f.render() for f in report.findings)


# -- op-log completeness (OL) -----------------------------------------------

def test_oplog_checker_flags_unlogged_mirror_mutations():
    report = run_fixtures(["oplog"])
    bad = {
        (f.code, f.symbol, f.detail)
        for f in report.findings
        if f.path.endswith("ol_bad.py")
    }
    assert bad == {
        ("OL001", "LeakySource.ol_silent_store", "arr_a"),
        ("OL001", "LeakySource.ol_silent_fill", "arr_b"),
        ("OL001", "LeakySource.ol_silent_rebind", "arr_c"),
        ("OL001", "LeakySource.ol_silent_scatter", "arr_a"),
        # protocol class, annotation rotted out of the static snapshot
        ("OL002", "LeakySource", "shadow"),
        # `# mirrored-array` on a class with no source protocol at all
        ("OL002", "RottedAnnotation", "orphan"),
    }, sorted(bad)


def test_oplog_checker_accepts_provenance_disciplines():
    # same-method _log/_bump helpers, direct oplog.append, the `!resync`
    # append, an epoch-bump rebuild, `# oplog-covered-by:` helpers, and
    # dynamic (chunked) snapshots with a live `# mirrored-array`
    report = run_fixtures(["oplog"])
    good = [f for f in report.findings if f.path.endswith("ol_good.py")]
    assert not good, [f.render() for f in good]


def test_oplog_repo_runs_clean():
    # the replication-readiness gate: every mirrored-field mutation in
    # emqx_tpu/ logs, resyncs, bumps, or declares its coverage
    report = run_analysis(ROOT / "emqx_tpu", checks=["oplog"])
    assert report.clean, "\n".join(f.render() for f in report.findings)


# -- version/epoch discipline (VC) ------------------------------------------

def test_version_checker_flags_missing_bumps_and_offloop_writes():
    report = run_fixtures(["version"])
    bad = {
        (f.code, f.symbol, f.detail)
        for f in report.findings
        if f.path.endswith("vc_bad.py")
    }
    assert ("VC001", "VcLeaky.vc_forget", "rows") in bad
    # version moved, but from the vc-bg thread with no declaration
    assert ("VC002", "VcThreaded.vc_bg_store", "cells") in bad
    assert len(bad) == 2, sorted(bad)


def test_version_checker_accepts_bump_closures_and_declared_writers():
    # injected `self._log`/`self._bump` callbacks, self-call bump
    # chains, `# oplog-covered-by:` helpers, and a `# single-writer:`
    # declared off-loop writer all stay silent
    report = run_fixtures(["version"])
    good = [f for f in report.findings if f.path.endswith("vc_good.py")]
    assert not good, [f.render() for f in good]


def test_version_repo_runs_clean():
    report = run_analysis(ROOT / "emqx_tpu", checks=["version"])
    assert report.clean, "\n".join(f.render() for f in report.findings)


# -- buffer-view escape (BV) ------------------------------------------------

def test_bufview_checker_flags_escaping_views():
    report = run_fixtures(["bufview"])
    bad = {
        (f.code, f.symbol, f.detail)
        for f in report.findings
        if f.path.endswith("bv_bad.py")
    }
    assert bad == {
        ("BV001", "BvSink.bv_keep_view", "view"),
        ("BV001", "BvSink.bv_keep_payload", "view"),
        # taint through the call graph (bv_make_view returns a view)
        ("BV001", "BvSink.bv_keep_indirect", "ref"),
        # annotated `# slab-escape` sink storing an un-owned parameter
        ("BV001", "BvSink.bv_park", "msg"),
        ("BV002", "BvSink.bv_rotted", "slab-escape"),
    }, sorted(bad)


def test_bufview_checker_accepts_owning_disciplines():
    # own-then-store, the getattr duck form, owning casts (bytes()),
    # and transient local scratch all stay silent
    report = run_fixtures(["bufview"])
    good = [f for f in report.findings if f.path.endswith("bv_good.py")]
    assert not good, [f.render() for f in good]


def test_bufview_repo_runs_clean():
    # the five slab-escape sites (session_store, mqueue, inflight,
    # retainer, workers) all own before storing; the slab accessor's
    # own memoryview is waived with justification in fabric.py
    report = run_analysis(ROOT / "emqx_tpu", checks=["bufview"])
    assert report.clean, "\n".join(f.render() for f in report.findings)


# -- scoped runs + parse parallelism ----------------------------------------

def test_parallel_parse_matches_serial():
    serial = run_analysis(FIXTURES, checks=["lock"])
    threaded = run_analysis(FIXTURES, checks=["lock"], jobs=4)
    assert (
        sorted(f.fingerprint for f in serial.findings)
        == sorted(f.fingerprint for f in threaded.findings)
    )
    assert threaded.files == serial.files


def test_only_paths_scopes_report_but_not_the_parse():
    full = run_analysis(FIXTURES, checks=["lock"])
    scoped = run_analysis(
        FIXTURES, checks=["lock"], only_paths=["analysis/lock_bad.py"]
    )
    assert scoped.files == full.files  # whole tree still parsed
    assert scoped.findings  # lock_bad findings survive the scope
    assert all(f.path == "analysis/lock_bad.py" for f in scoped.findings)
    other = {f.path for f in full.findings} - {"analysis/lock_bad.py"}
    assert not other or all(
        f.path != p for f in scoped.findings for p in other
    )


# -- the tier-1 repo gate ---------------------------------------------------

def test_repo_is_clean_of_non_baseline_findings():
    baseline = Baseline.load(ROOT / "tools" / "analysis" / "baseline.json")
    report = run_analysis(ROOT / "emqx_tpu", baseline=baseline)
    assert report.clean, "\n".join(f.render() for f in report.findings)
    # the baseline must not rot: every entry still matches a real finding
    assert not report.stale_baseline, report.stale_baseline


def test_repo_scan_is_fast_enough_for_ci():
    report = run_analysis(ROOT / "emqx_tpu")
    assert report.elapsed < 30.0, report.elapsed
    assert report.files > 100  # it really scanned the tree


# -- CLI contract -----------------------------------------------------------

def _cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "tools.analysis", *args],
        cwd=ROOT,
        capture_output=True,
        text=True,
        timeout=120,
    )


def test_cli_exit_codes_and_json():
    # findings -> 1, with machine-readable output
    p = _cli(str(FIXTURES), "--format", "json", "--no-baseline")
    assert p.returncode == 1, p.stderr
    doc = json.loads(p.stdout)
    assert doc["clean"] is False
    assert {f["code"] for f in doc["findings"]} >= {
        "LK001", "AB001", "JP001", "CK001", "MN001",
    }
    # clean tree -> 0 (the metrics fixture's good half, checked alone,
    # has no violations in lock scope)
    p = _cli(str(FIXTURES), "--checks", "lock", "--format", "json")
    assert p.returncode == 1  # lock_bad still fails
    # internal error (bogus root) -> 2
    p = _cli(str(FIXTURES / "does_not_exist"))
    assert p.returncode == 2


def test_cli_clean_tree_exits_zero(tmp_path):
    mod = tmp_path / "clean.py"
    mod.write_text("def fine():\n    return 1\n")
    p = _cli(str(tmp_path))
    assert p.returncode == 0, p.stdout + p.stderr
    assert "0 finding(s)" in p.stdout


def test_cli_jobs_and_changed_only_flags():
    p = _cli(str(FIXTURES), "--jobs", "4", "--checks", "lock",
             "--format", "json", "--no-baseline")
    assert p.returncode == 1, p.stderr  # same findings, parallel parse
    doc = json.loads(p.stdout)
    assert any(f["code"] == "LK001" for f in doc["findings"])
    # --changed-only runs against this repo's git; the working tree may
    # be clean or dirty, but changed files must never violate the lint
    p = _cli("--changed-only")
    assert p.returncode == 0, p.stdout + p.stderr


# -- wire-format registry discipline (WF) -----------------------------------

def test_wire_checker_flags_unregistered_and_drifted_formats():
    report = run_fixtures(["wire"])
    bad = {
        (f.code, f.detail)
        for f in report.findings
        if f.path.endswith("wf_bad.py")
    }
    # an unregistered struct at a serialize boundary
    assert ("WF001", "BAD_HDR") in bad
    # the acceptance-criteria case: a test-only FIELD REORDER in a
    # registered dtype, caught without running any broker code
    assert ("WF002", "fix.wf.reordered") in bad
    # digest drifted from the golden pin without a version bump
    assert ("WF003", "fix.wf.drifted") in bad
    # registered but never pinned / pinned at a stale version
    assert ("WF004", "fix.wf.unpinned:unpinned") in bad
    assert ("WF004", "fix.wf.stale:stale-pin") in bad
    assert len(bad) == 5, sorted(bad)


def test_wire_checker_accepts_registered_and_pinned():
    report = run_fixtures(["wire"])
    good = [f for f in report.findings if f.path.endswith("wf_good.py")]
    assert not good, [f.render() for f in good]


def test_wire_repo_runs_clean():
    # every module-level wire literal at a serialize boundary in
    # emqx_tpu/ is registered, digest-matched, and pinned
    report = run_analysis(ROOT / "emqx_tpu", checks=["wire"])
    assert report.clean, "\n".join(f.render() for f in report.findings)


# -- snapshot-schema discipline (SS) -----------------------------------------

def test_snapshot_checker_flags_schema_and_getstate_drift():
    report = run_fixtures(["snapshot"])
    bad = {
        (f.code, f.symbol, f.detail)
        for f in report.findings
        if f.path.endswith("ss_bad.py")
    }
    # a snapshot root emitting a key the registry never versioned
    assert ("SS001", "snap_func", "fix.ss.snapshot") in bad
    # registration whose source function rotted away
    assert ("SS002", "<module>", "fix.ss.gone") in bad
    # the PR 10 bug class: a declared-dropped device handle no longer
    # nulled in __getstate__
    assert ("SS003", "DeviceThing", "fix.ss.device_class:mesh") in bad
    assert len(bad) == 3, sorted(bad)


def test_snapshot_checker_accepts_matching_shapes():
    report = run_fixtures(["snapshot"])
    good = [f for f in report.findings if f.path.endswith("ss_good.py")]
    assert not good, [f.render() for f in good]


def test_snapshot_repo_runs_clean():
    report = run_analysis(ROOT / "emqx_tpu", checks=["snapshot"])
    assert report.clean, "\n".join(f.render() for f in report.findings)


# -- BPAPI sender/receiver symmetry (BP) -------------------------------------

def test_bpapi_checker_flags_every_asymmetry():
    report = run_fixtures(["bpapi"])
    bad = {
        (f.code, f.detail)
        for f in report.findings
        if f.path.endswith("bp_bad.py")
    }
    # sent but in no registered proto table
    assert ("BP001", "fxbad.vanished") in bad
    # registered (and not serve-only) but never sent
    assert ("BP002", "fxbad.orphan") in bad
    # in-code table drifted from the declared one / undeclared version
    assert ("BP003", "fxbad.v1") in bad
    assert ("BP003", "fxbad.v2:undeclared") in bad
    # tag-family asymmetries: sent-no-handler, registered-but-dead, and
    # a boundary tuple whose head no family knows
    assert ("BP004", "fix.bp.bad_tags:fxdead:no-handler") in bad
    assert ("BP004", "fix.bp.bad_tags:fxghost:no-sender") in bad
    assert ("BP004", "fix.bp.bad_tags:fxghost:no-handler") in bad
    assert ("BP004", "head:fxrogue:sent-unregistered") in bad
    assert len(bad) == 8, sorted(bad)


def test_bpapi_checker_accepts_symmetric_tables():
    # serve-only exemption, assigned-then-sent tuples, and propagation
    # through parameter seams all stay silent
    report = run_fixtures(["bpapi"])
    good = [f for f in report.findings if f.path.endswith("bp_good.py")]
    assert not good, [f.render() for f in good]


def test_bpapi_repo_runs_clean():
    # every cluster op tag sent in emqx_tpu/ has a handler and vice
    # versa; the in-code rpc tables match the frozen BPAPI declaration
    report = run_analysis(ROOT / "emqx_tpu", checks=["bpapi"])
    assert report.clean, "\n".join(f.render() for f in report.findings)
