"""The reduction from a profiler trace to busy, idle and per-program time:
on hand-made planes whose answer is known, and on one small trace recorded on
the chip (benchmark/tests/record_trace.py, TPU v5 lite, PR 23)."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import trace_reduce  # noqa: E402
from readers import trace_reduce as reader  # noqa: E402

MS = 1_000_000


def planes():
    ops = [("fusion.1", 10 * MS, 2 * MS), ("copy.2", 11 * MS, 3 * MS),  # overlap
           ("fusion.1", 30 * MS, 5 * MS)]
    modules = [("jit_shape_route_step(123)", 10 * MS, 4 * MS),
               ("jit_shape_route_step(123)", 30 * MS, 5 * MS),
               ("jit_dynamic_slice(9)", 36 * MS, 1 * MS)]
    host = [("outer_loop", 0, 100 * MS), ("wait_for_publish", 15 * MS, 14 * MS),
            ("tiny", 20 * MS, 1 * MS)]
    return [("/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", modules),
                               ("Steps", [("1", 0, 100 * MS)])]),
            ("/host:CPU", [("python", host)])]


def test_union_merges_overlaps_and_finds_gaps():
    busy, gaps = trace_reduce.union_seconds(
        [(0, 10), (5, 20), (30, 40), (40, 45), (100, 101)])
    assert busy == pytest.approx(36e-9)
    assert gaps == [(20, 30), (45, 100)]


def test_busy_idle_programs_and_gap_labels():
    r = trace_reduce.reduce_planes(planes())
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.009)  # 10-14 ms and 30-35 ms
    assert r["idle_share"] == pytest.approx(0.91)
    assert r["programs"]["jit_shape_route_step"] == {
        "seconds": pytest.approx(0.009), "count": 2}
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.007)]
    gaps = dict((round(s, 3), lab) for lab, s in r["idle_gaps"])
    assert gaps[0.065] == "outer_loop"        # 35-100 ms: only the outer event
    assert gaps[0.016] == "wait_for_publish"  # 14-30 ms: the most specific
    ctx = {"trace": r}
    assert reader.read({"field": "idle_share"}, ctx) == pytest.approx(91.0)
    assert reader.read({"field": "program_ms", "match": "route_step"},
                       ctx) == pytest.approx(4.5)
    assert reader.read({"field": "program_ms", "match": "absent"}, ctx) is None
    assert reader.read({"field": "idle_share"}, {"trace": None}) is None


def test_the_capture_s_own_start_and_stop_are_outside_the_window():
    ps = planes()
    ps[1][1][0][1].extend([("$profiler.py:101 start_trace", 0, 5 * MS),
                           ("$profiler.py:213 stop_trace", 60 * MS, 40 * MS)])
    r = trace_reduce.reduce_planes(ps)
    assert r["window_s"] == pytest.approx(0.055)  # 5 ms to 60 ms
    assert r["busy_s"] == pytest.approx(0.009)
    assert max(s for _, s in r["idle_gaps"]) == pytest.approx(0.025)  # 35-60 ms


def test_a_trace_without_a_device_plane_reads_nothing():
    assert trace_reduce.reduce_planes([p for p in planes()
                                       if p[0].startswith("/host")]) is None


def test_recorded_chip_trace():
    path = os.path.join(HERE, "data", "small.xplane.pb")
    pytest.importorskip("jax")
    r = trace_reduce.reduce_planes(trace_reduce.load(path))
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0.0 < r["idle_share"] < 1.0
    stub = [v for k, v in r["programs"].items() if "route_step_stub" in k]
    assert len(stub) == 1 and stub[0]["count"] == 5
    assert stub[0]["seconds"] <= r["busy_s"] * 1.0001
    assert any(lab == "host_pause" for lab, _ in r["idle_gaps"])
