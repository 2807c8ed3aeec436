"""`share_4n.sat`: `share_1m_full`'s groups on `cluster_4n`'s four nodes.
The configuration is the two files' blocks put together; the manifest holds
the cell and its metrics (compared by name: a later PR may append more); the
four-node CPU rehearsal is `correct`, every metric the cell reads finds a
number in it, and each control judged in the program's place is not
`correct`; on a program without the new series the three new readers read
nothing and none raises."""

import argparse
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

CELL = "share_4n.sat"
NEW = ("share.leader_deferred_share", "cluster.shared_apply_us",
       "cluster.shared_pairs_min")
# the accepted metrics the cell reads too: over the nodes, or summed
JOINED = ("cluster.", "node.", "share.")
# the one-node metrics it reads over the nodes' summed scrapes (ratios of
# sums: the deployment's mean), and the load generator's own share
ONE_NODE = ("loadgen.cpu_share_max", "ingest.batch_rows_mean", "ingest.queue_wait_ms",
            "ingest.finish_ms", "route.device_share", "tables.prepare_ms",
            "step.roundtrip_ms", "step.launch_ms", "step.execute_wait_ms",
            "step.readback_ms", "step.window_compiles", "step.launch_compiles",
            "step.readback_compiles", "fanout.host_dispatch_us", "fanout.send_us",
            "fanout.deliveries_per_run", "egress.packets_per_write",
            "egress.split_share", "owner.publish_in_us", "owner.ack_in_us",
            "owner.acks_per_run", "transport.decode_us", "session.mqueue_dropped",
            "device.hbm_peak_mb")


def load(kind, name):
    with open(os.path.join(ROOT, "benchmark", kind, name + ".json")) as f:
        return json.load(f)


def read(name, ctx):
    spec = load("metrics", name)
    assert spec["name"] == name
    return importlib.import_module("readers." + spec["reader"]).read(spec["args"], ctx)


def test_the_configuration_is_share_1m_fulls_groups_on_cluster_4ns_nodes():
    config, full, c4 = (load("configs", n) for n in ("share_4n", "share_1m_full",
                                                      "cluster_4n"))
    for key in ("rehearsal_table", "assumed", "connections", "listener_split"):
        assert config[key] == full[key], key
    # the one cut: {j} over 64 values, not 250 (256 filters a group)
    table = json.loads(json.dumps(full["table"]))
    table["j_space"] = table["families"][0]["per_id"] = 64
    assert config["table"] == table
    assert config["subscriptions"] == 250 * 4 * 4 * 64 + 4 * 10 == 256_040
    for key in ("session", "shared_subscription", "listeners", "observe"):
        assert config["broker"][key] == full["broker"][key], key
    assert config["nodes"] == c4["nodes"]
    assert config["broker"]["cluster"] == c4["broker"]["cluster"]
    assert config["broker"]["cluster"]["rpc_mode"] == "sync"
    assert config["guarantees"]["forward"] == c4["guarantees"]["forward"]
    assert sorted(config["reduced"]) == ["connections", "nodes", "subscriptions"]
    assert len(config["source"]) <= 200
    sat, mine = load("traffic", "share_1m_full.sat"), load("traffic", CELL)
    # share_1m_full.sat's loop, topics, checks and warm-up stages (buckets
    # 4,096 / 1,024 / 2,048); cluster_4n.sat's rehearsal
    assert {k for k in set(sat) | set(mine) if sat.get(k) != mine.get(k)} \
        == {"name", "rehearsal"}
    assert [st.get("bucket") for st in mine["warmup"]] == [4096, 1024, 2048, None]
    assert mine["rehearsal"] == load("traffic", "cluster_4n.sat")["rehearsal"]


def test_the_manifest_holds_the_cell_and_its_metrics():
    manifest, faults = manifest_check.load_and_check(ROOT)
    assert faults == []
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("share_4n", CELL, 4)
    config = next(c for c in manifest["configs"] if c["name"] == "share_4n")
    assert config["reduced"] == sorted(load("configs", "share_4n")["reduced"])
    assert config["source"] == load("configs", "share_4n")["source"]
    by_name = {e["name"]: e for e in manifest["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
    assert by_name["share.leader_deferred_share"]["layer"] == "host_fanout"
    assert {by_name[n]["layer"] for n in NEW[1:]} == {"cluster_routes"}
    for name, e in by_name.items():
        if name.startswith(JOINED) and "cluster_4n.sat" in e["workloads"] \
                or name.startswith("share.") and "share_1m_full.sat" in e["workloads"]:
            assert CELL in e["workloads"], name
    for name in ONE_NODE:
        assert CELL in by_name[name]["workloads"], name


def capture_context(monkeypatch):
    seen = {}
    inner = bench_run.context

    def context(*args):
        seen["ctx"] = inner(*args)
        return seen["ctx"]
    monkeypatch.setattr(bench_run, "context", context)
    return seen


def rehearse(control, seed, hooks=None):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=3.0, trace=0,
                              rehearse_cpu=True, control=control, traffic=None)

    def traffic(t):
        t["drain_s"] = 6.0
        t["settle_s"] = 1.0
    return bench_run.run(args, {"traffic": traffic, **(hooks or {})})


def test_the_rehearsal_is_correct_every_metric_reads_and_every_member_fails_it(
        monkeypatch):
    seen = capture_context(monkeypatch)
    r = rehearse("every_member", 2_500_000_043)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["chip_run"] is False and r["metrics"] == {}
    assert r["device"]["count"] == 4 and len(r["device"]["nodes"]) == 4
    assert r["counts"]["share"]["groups_receiving"] == 6
    assert r["checks"]["unexpected"]["value"] == 0
    assert r["control"]["name"] == "every_member"
    assert r["control"]["correct"] is False
    assert r["control"]["checks"]["unexpected"][0] > 0
    ctx = seen["ctx"]
    for node in ctx["nodes"]:  # the CPU has no device trace: a hand-made one
        node["trace"] = {"idle_share": 0.5}
    manifest, _ = manifest_check.load_and_check(ROOT)
    mine = [e["name"] for e in manifest["per_layer"] if CELL in e["workloads"]]
    values = {name: read(name, ctx) for name in mine}
    assert all(v is not None for v in values.values()), values
    # every group spans two or three nodes, so each (message, group) is
    # led by one of them and deferred by the rest
    assert 0.4 < values["share.leader_deferred_share"] < 0.7
    # the rehearsal table: 4 groups x 32 filters + 2 fleet-wide groups
    assert values["cluster.shared_pairs_min"] == 130
    assert values["cluster.shared_apply_us"] > 0
    assert values["share.picks_per_launch"] > 0


def test_one_member_fails_the_rehearsal():
    r = rehearse("one_member", 2_500_000_047)
    assert r["correct"] is True
    assert r["control"]["correct"] is False
    value, limit = r["control"]["checks"]["share_member_share_max"]
    assert value > limit


def test_on_a_program_without_the_series_nothing_raises():
    prom0, prom1 = {"emqx_messages_received": 1.0}, {"emqx_messages_received": 9.0}
    one = {"name": "", "prom0": prom0, "prom1": prom1, "proc": {}, "trace": None}
    for ctx in ({"prom0": prom0, "prom1": prom1, "trace": None, "nodes": [one] * 4},
                {"prom0": None, "prom1": None, "trace": None, "nodes": []}):
        assert [read(name, ctx) for name in NEW] == [None, None, None]


def test_the_new_metrics_read_a_scrape():
    sec = "emqx_profile_section_cluster_shared_apply_seconds_"

    def node(led, deferred, pairs, apply_s, ops):
        return {"prom0": {"emqx_shared_leader_led": 0.0,
                          "emqx_shared_leader_deferred": 0.0},
                "prom1": {"emqx_shared_leader_led": led,
                          "emqx_shared_leader_deferred": deferred,
                          "emqx_shared_cluster_pairs": pairs,
                          sec + "sum": apply_s, sec + "count": ops}}
    nodes = [node(100.0, 300.0, 250_004.0, 1.5, 750_012.0),
             node(300.0, 900.0, 250_004.0, 0.75, 750_012.0),
             node(0.0, 0.0, 250_000.0, 0.0, 0.0)]
    ctx = {"prom0": bench_run.server.sum_scrapes([n["prom0"] for n in nodes]),
           "prom1": bench_run.server.sum_scrapes([n["prom1"] for n in nodes]),
           "nodes": nodes}
    assert read("share.leader_deferred_share", ctx) == pytest.approx(0.75)
    assert read("cluster.shared_pairs_min", ctx) == 250_000
    # total over count node by node; a node that applied nothing is left out
    assert read("cluster.shared_apply_us", ctx) == pytest.approx(
        (1.5 + 0.75) / 2 / 750_012 * 1e6)
