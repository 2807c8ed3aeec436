"""`share_1m_full`: BASELINE config 4 at the million ISSUE 33 stated (PR 34).
Its table is `test_share.py`'s FULL; its files are `share_1m`'s but for the
keys that carry the size; its four per-layer metrics are data over readers that
were there; a CPU rehearsal of its cell is `correct`."""

import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from harness import manifest_check  # noqa: E402
from harness.traffic import Table  # noqa: E402
from test_correct import rehearse  # noqa: E402
from test_share import FULL, SIX, load  # noqa: E402

CELL = "share_1m_full.sat"
NEW = ["share.picked_dispatch_us", "share.picks_per_launch", "share.subscribe_us",
       "share.group_table_uploads"]


def test_the_table_is_the_full_one():
    config = load("configs", "share_1m_full")
    assert config["table"] == FULL
    table = Table(config["table"], 7)
    assert (table.n_filters(), table.n_class_filters()) == (1_000_040, 250_004)
    assert table.n_filters() == config["subscriptions"]
    assert table.n_sub + load("traffic", CELL)["publishers"] == config["connections"]


def test_the_files_differ_from_share_1ms_only_in_what_carries_the_size():
    cut, full = load("configs", "share_1m"), load("configs", "share_1m_full")
    differ = {k for k in set(cut) | set(full) if cut.get(k) != full.get(k)}
    assert differ == {"name", "source", "deployment", "reduced", "device_memory",
                      "subscriptions", "table"}
    assert sorted(full["reduced"]) == ["connections", "subscriptions"]
    assert full["reduced"]["connections"] == cut["reduced"]["connections"]
    t_cut, t_full = cut["table"], full["table"]
    assert t_full["j_space"] == t_full["families"][0]["per_id"] == 250
    t_full["j_space"] = t_full["families"][0]["per_id"] = 16
    assert t_full == t_cut
    a, b = load("traffic", "share_1m.sat"), load("traffic", CELL)
    assert {k for k in a if a[k] != b[k]} == {"name"} and set(a) == set(b)


def test_the_manifest_holds_the_cell_and_its_metrics():
    manifest, faults = manifest_check.load_and_check(ROOT)
    assert faults == []
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("share_1m_full", CELL, 1)
    config = next(c for c in manifest["configs"] if c["name"] == "share_1m_full")
    assert config["reduced"] == ["connections", "subscriptions"]
    assert config["source"] == load("configs", "share_1m_full")["source"]
    assert config["source"] != next(
        c for c in manifest["configs"] if c["name"] == "share_1m")["source"]
    mine = [e for e in manifest["per_layer"] if CELL in e.get("workloads", ())]
    # every metric share_1m.sat reads, and the four that only groups have
    assert [e["name"] for e in mine] == [
        e["name"] for e in manifest["per_layer"] if "share_1m.sat" in e["workloads"]]
    assert len(mine) == 35 + len(NEW)
    assert [e["name"] for e in manifest["per_layer"][-len(NEW):]] == NEW
    for e in manifest["per_layer"][-len(NEW):]:
        assert e["workloads"] == ["share_1m.sat", CELL]


def read(name, ctx):
    spec = load("metrics", name)
    return importlib.import_module("readers." + spec["reader"]).read(spec["args"], ctx)


def test_the_four_metrics_read_a_scrape():
    sec = "emqx_profile_section_"
    prom0 = {sec + "shared_dispatch_picked_seconds_sum": 1.0,
             sec + "shared_dispatch_picked_seconds_count": 100_000,
             sec + "broker_share_subscribe_seconds_sum": 80.0,
             sec + "broker_share_subscribe_seconds_count": 1_000_040,
             "emqx_shared_picks": 100_000, "emqx_ingest_batch_size_count": 50,
             "emqx_grouptab_uploads": 2}
    prom1 = {**prom0, sec + "shared_dispatch_picked_seconds_sum": 16.0,
             sec + "shared_dispatch_picked_seconds_count": 1_100_000,
             "emqx_shared_picks": 1_100_000, "emqx_ingest_batch_size_count": 150,
             "emqx_grouptab_uploads": 5}
    ctx = {"prom0": prom0, "prom1": prom1, "nodes": [{"prom0": prom0, "prom1": prom1}]}
    assert read("share.picked_dispatch_us", ctx) == pytest.approx(15.0)
    assert read("share.picks_per_launch", ctx) == pytest.approx(10_000.0)
    assert read("share.subscribe_us", ctx) == pytest.approx(80e6 / 1_000_040)
    assert read("share.group_table_uploads", ctx) == 5


def test_on_a_program_without_the_series_nothing_raises():
    """The parent counts no pick: a mean and a gauge have nothing to read, the
    change of a counter that is not there reads 0."""
    plain = {"emqx_messages_received": 1.0, "emqx_ingest_batch_size_count": 3}
    late = {"emqx_messages_received": 9.0, "emqx_ingest_batch_size_count": 8}
    ctx = {"prom0": plain, "prom1": late, "trace": None,
           "nodes": [{"prom0": plain, "prom1": late}]}
    assert [read(name, ctx) for name in NEW] == [None, 0.0, None, None]


def test_a_rehearsal_of_the_cell_is_correct():
    r = rehearse(CELL, {})
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["chip_run"] is False and r["metrics"] == {}
    assert list(r["checks"]) == SIX + ["share_member_share_max"]
    assert r["counts"]["window_fan_mean"] == 3.0
    assert r["counts"]["share"]["groups_receiving"] == 6
    assert r["checks"]["share_member_share_max"]["value"] < 1.5
