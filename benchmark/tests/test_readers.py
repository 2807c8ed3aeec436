"""The two readers PR 25 adds, on hand-made contexts, the data files of its
new metrics against a hand-made scrape, and the manifest with them in it."""

import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))

from harness import manifest_check  # noqa: E402
from readers import idle_gap_share, prom_last  # noqa: E402

NEW = ("owner.loop_idle_share", "owner.unattributed_share", "owner.stall_s",
       "owner.gc_pause_s", "owner.publish_in_us", "owner.ack_in_us",
       "transport.decode_us", "fanout.send_us", "ingest.finish_ms",
       "step.launch_compiles", "step.readback_compiles",
       "session.mqueue_dropped", "device.hbm_peak_mb", "idle.attributed_share")


def test_prom_last_reads_the_second_scrape():
    ctx = {"prom0": {"emqx_device_hbm_peak_bytes": 1e6},
           "prom1": {"emqx_device_hbm_peak_bytes": 3e8}}
    args = {"series": "emqx_device_hbm_peak_bytes", "scale": 1e-6}
    assert prom_last.read(args, ctx) == pytest.approx(300.0)
    # the parent commit exports no such gauge: nothing, and no error
    assert prom_last.read(args, {"prom0": {}, "prom1": {}}) is None
    assert prom_last.read(args, {"prom1": None}) is None


def test_idle_gap_share_counts_seconds_by_label():
    gaps = [["emqx:ingest.wait", 3.0], ["emqx:host_dispatch", 0.5],
            ["TpuCompiler::Compile", 0.4], ["unattributed", 0.1]]
    args = {"prefix": "emqx:"}
    assert idle_gap_share.read(args, {"trace": {"idle_gaps": gaps}}) == \
        pytest.approx(87.5)
    # the parent's traced run: gaps, none of them named by the program
    assert idle_gap_share.read(
        args, {"trace": {"idle_gaps": [["__unknown__get", 1.7]]}}) == 0.0
    assert idle_gap_share.read(args, {"trace": None}) is None
    assert idle_gap_share.read(args, {"trace": {"idle_gaps": []}}) is None


def read(name, ctx):
    with open(os.path.join(ROOT, "benchmark", "metrics", name + ".json")) as f:
        spec = json.load(f)
    assert spec["name"] == name
    reader = importlib.import_module("readers." + spec["reader"])
    return reader.read(spec["args"], ctx)


def scrape(select, run, other, stall, gc, sections, counters, peak):
    out = {"emqx_owner_loop_select_seconds_sum": select,
           "emqx_owner_loop_run_seconds_sum": run,
           "emqx_owner_loop_other_seconds_sum": other,
           "emqx_owner_loop_stall_seconds_sum": stall,
           "emqx_owner_gc_pause_seconds_sum": gc,
           "emqx_device_hbm_peak_bytes": peak}
    for name, (seconds, count) in sections.items():
        out[f"emqx_profile_section_{name}_seconds_sum"] = seconds
        out[f"emqx_profile_section_{name}_seconds_count"] = count
    out.update(counters)
    return out


def test_the_new_metrics_data_files_read_a_scrape():
    prom0 = scrape(10.0, 20.0, 1.0, 0.0, 0.1,
                   {"channel_publish_in": (1.0, 10_000),
                    "channel_ack_in": (2.0, 100_000),
                    "ingress_decode": (0.5, 100_000),
                    "egress_send": (1.0, 100_000),
                    "ingest_finish": (0.1, 10)},
                   {"emqx_device_compile_in_launch_count": 3,
                    "emqx_device_compile_in_readback_count": 40,
                    "emqx_session_mqueue_dropped": 0}, 2e8)
    prom1 = scrape(13.0, 47.0, 2.5, 3.5, 0.6,
                   {"channel_publish_in": (2.5, 60_000),
                    "channel_ack_in": (6.0, 200_000),
                    "ingress_decode": (0.9, 200_000),
                    "egress_send": (3.0, 300_000),
                    "ingest_finish": (0.7, 30)},
                   {"emqx_device_compile_in_launch_count": 3,
                    "emqx_device_compile_in_readback_count": 52,
                    "emqx_session_mqueue_dropped": 7}, 3.5e8)
    ctx = {"prom0": prom0, "prom1": prom1, "trace": {"idle_gaps": [
        ["emqx:ingest.wait", 9.0], ["unattributed", 1.0]]}}
    want = {"owner.loop_idle_share": 10.0,       # 3 s of select in 30 s
            "owner.unattributed_share": 100 * 1.5 / 27.0,
            "owner.stall_s": 3.5, "owner.gc_pause_s": 0.5,
            "owner.publish_in_us": 30.0, "owner.ack_in_us": 40.0,
            "transport.decode_us": 4.0, "fanout.send_us": 10.0,
            "ingest.finish_ms": 30.0, "step.launch_compiles": 0.0,
            "step.readback_compiles": 12.0, "session.mqueue_dropped": 7.0,
            "device.hbm_peak_mb": 350.0, "idle.attributed_share": 90.0}
    assert set(want) == set(NEW)
    for name, value in want.items():
        assert read(name, ctx) == pytest.approx(value), name


def test_on_a_program_without_the_series_nothing_raises():
    """The parent commit exports none of the new series: a ratio or a mean
    has nothing to read, a plain change reads 0, and no reader raises."""
    ctx = {"prom0": {"emqx_messages_received": 1.0},
           "prom1": {"emqx_messages_received": 9.0}, "trace": None}
    for name in NEW:
        assert read(name, ctx) in (None, 0.0), name


def test_the_manifest_holds_the_new_entries_and_is_sound():
    manifest, faults = manifest_check.load_and_check(ROOT)
    assert faults == []
    by_name = {e["name"]: e for e in manifest["per_layer"]}
    for name in NEW:
        assert by_name[name]["moves"] == "deliveries_per_s"
        assert by_name[name]["workloads"] == ["mixed_1m.sat", "fanout_1k.sat"]
    # appended, so that nothing that was there reads as changed
    assert [e["name"] for e in manifest["per_layer"]][-len(NEW):] == list(NEW)
