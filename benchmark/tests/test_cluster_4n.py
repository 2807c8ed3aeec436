"""`cluster_4n.sat`: the four-node rehearsal on the CPU is correct and every
per-layer metric the cell brings reads a number from it; with the forwards
between the nodes dropped (PR 27's proxy) it is not correct; the `per_node`
reader's reductions, on hand-made contexts; and on a program without the new
series (the parent's) every new reader reads nothing, or a plain 0, and none
raises."""

import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402
from harness import manifest_check  # noqa: E402
from readers import per_node  # noqa: E402
from test_correct import rehearse  # noqa: E402
from test_nodes import ForwardDroppingProxy  # noqa: E402

CELL = "cluster_4n.sat"
NEW = ("cluster.forward_out_us", "cluster.forward_in_us",
       "cluster.forward_confirm_ms", "cluster.forward_batch_msgs",
       "cluster.forward_retries", "cluster.forward_duplicates",
       "cluster.forward_unconfirmed", "cluster.route_ops_per_batch",
       "cluster.route_apply_ms", "node.device_idle_share_min",
       "node.owner_loop_idle_share_min", "node.gc_pause_s_max",
       "node.deliveries_share_max")


def read(name, ctx):
    with open(os.path.join(ROOT, "benchmark", "metrics", name + ".json")) as f:
        spec = json.load(f)
    assert spec["name"] == name
    reader = importlib.import_module("readers." + spec["reader"])
    return reader.read(spec["args"], ctx)


def test_the_manifest_holds_the_cell_its_configuration_and_its_metrics():
    manifest, faults = manifest_check.load_and_check(ROOT)
    assert faults == []
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["name"], cell["config"], cell["chips"]) == (CELL, "cluster_4n", 4)
    by_name = {e["name"]: e for e in manifest["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
    assert {by_name[n]["moves"] for n in NEW if n.startswith("cluster.route")} \
        == {"setup_s"}
    # the configuration is mixed_1m's table and session limits on four nodes
    mixed = bench_run.load_json("configs", "mixed_1m.json")
    config = bench_run.load_json("configs", "cluster_4n.json")
    for key in ("table", "rehearsal_table", "listener_split", "assumed"):
        assert config[key] == mixed[key], key
    assert config["broker"]["session"] == mixed["broker"]["session"]
    assert config["nodes"]["count"] == 4
    assert config["broker"]["cluster"]["rpc_mode"] == "sync"
    assert sorted(config["reduced"]) == sorted(next(
        c for c in manifest["configs"] if c["name"] == "cluster_4n")["reduced"])
    sat = bench_run.load_json("traffic", "mixed_1m.sat.json")
    mine = bench_run.load_json("traffic", CELL + ".json")
    for key in sat:  # one mix on one node and on four
        if key not in ("name", "rehearsal"):
            assert mine[key] == sat[key], key


def capture_context(monkeypatch):
    seen = {}
    inner = bench_run.context

    def context(*args):
        seen["ctx"] = inner(*args)
        return seen["ctx"]
    monkeypatch.setattr(bench_run, "context", context)
    return seen


def test_the_four_node_rehearsal_is_correct_and_every_new_metric_reads(monkeypatch):
    seen = capture_context(monkeypatch)
    r = rehearse(CELL, {}, seed=2_500_000_029)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["chip_run"] is False and r["metrics"] == {}
    assert r["device"]["count"] == 4 and len(r["device"]["nodes"]) == 4
    cluster = r["counts"]["cluster"]
    assert cluster["subscriptions"] == [33] * 4 and cluster["routes_per_node"] == 132
    assert 0.6 < cluster["cross_node_share"] < 0.9
    ctx = seen["ctx"]
    assert len(ctx["nodes"]) == 4
    for node in ctx["nodes"]:  # the CPU has no device trace: a hand-made one
        node["trace"] = {"idle_share": 0.9 - 0.1 * ctx["nodes"].index(node)}
    values = {name: read(name, ctx) for name in NEW}
    assert all(v is not None for v in values.values()), values
    assert values["cluster.forward_batch_msgs"] >= 1.0
    assert values["cluster.forward_out_us"] > 0 and values["cluster.forward_in_us"] > 0
    assert values["cluster.forward_confirm_ms"] > 0
    assert values["cluster.forward_retries"] == 0
    assert values["cluster.forward_duplicates"] == 0
    assert values["cluster.route_ops_per_batch"] >= 1.0
    assert values["cluster.route_apply_ms"] > 0
    assert values["node.device_idle_share_min"] == pytest.approx(60.0)
    assert 25.0 <= values["node.deliveries_share_max"] <= 100.0
    # every node applied the other three's routes: 99 of the 132
    assert [n["prom1"]["emqx_cluster_route_ops"] for n in ctx["nodes"]] == [99.0] * 4


@pytest.mark.parametrize("seed", [13, 2_500_000_031])
def test_with_the_forwards_between_the_nodes_dropped_it_is_not_correct(seed):
    proxies = []

    def via(port):
        proxies.append(ForwardDroppingProxy(port))
        return proxies[-1].port
    r = rehearse(CELL, {"cluster_port": via}, seed)
    assert sum(p.dropped for p in proxies) >= 1
    checks = r["checks"]
    assert checks["missing"]["value"] + checks["unacked"]["value"] >= 1
    assert r["correct"] is False


def node(delivered0, delivered1, gc0, gc1, ops, batches, idle=None):
    return {"name": "n", "proc": {},
            "prom0": {"emqx_messages_delivered": delivered0,
                      "emqx_owner_gc_pause_seconds_sum": gc0},
            "prom1": {"emqx_messages_delivered": delivered1,
                      "emqx_owner_gc_pause_seconds_sum": gc1,
                      "emqx_cluster_route_ops": ops,
                      "emqx_cluster_route_batches": batches},
            "trace": None if idle is None else {"idle_share": idle}}


def test_per_node_reduces_a_named_reader_over_the_nodes():
    ctx = {"nodes": [node(0.0, 700.0, 1.0, 4.5, 3000.0, 3.0, 0.8),
                     node(100.0, 200.0, 2.0, 2.5, 1000.0, 2.0, 0.95),
                     node(0.0, 200.0, 0.0, 0.0, 0.0, 0.0)]}
    delivered = {"reader": "prom_delta_ratio",
                 "args": {"num": ["emqx_messages_delivered"]}}
    assert per_node.read({**delivered, "reduce": "max"}, ctx) == 700.0
    assert per_node.read({**delivered, "reduce": "min"}, ctx) == 100.0
    assert per_node.read({**delivered, "reduce": "mean"}, ctx) == pytest.approx(1000 / 3)
    assert per_node.read({**delivered, "reduce": "max_share", "scale": 100.0},
                         ctx) == pytest.approx(70.0)
    assert read("node.deliveries_share_max", ctx) == pytest.approx(70.0)
    assert read("node.gc_pause_s_max", ctx) == pytest.approx(3.5)
    # a node whose capture holds nothing is left out
    assert read("node.device_idle_share_min", ctx) == pytest.approx(80.0)
    # totals over totals, node by node; a node without a batch is left out
    assert read("cluster.route_ops_per_batch", ctx) == pytest.approx(750.0)
    with pytest.raises(ValueError):
        per_node.read({**delivered, "reduce": "median"}, ctx)
    assert per_node.read({**delivered, "reduce": "max_share"},
                         {"nodes": [node(5.0, 5.0, 0, 0, 0, 0)]}) is None


def test_on_a_program_without_the_series_nothing_raises():
    """The parent exports none of the new series, and a context of one node
    or of none has to read as nothing too."""
    prom0, prom1 = {"emqx_messages_received": 1.0}, {"emqx_messages_received": 9.0}
    one = {"name": "", "prom0": prom0, "prom1": prom1, "proc": {}, "trace": None}
    for ctx in ({"prom0": prom0, "prom1": prom1, "trace": None, "nodes": [one]},
                {"prom0": prom0, "prom1": prom1, "trace": None},
                {"prom0": None, "prom1": None, "trace": None, "nodes": []}):
        for name in NEW:
            assert read(name, ctx) in (None, 0.0), name
