"""`plus_100k`: BASELINE config 2 (PR 37). Its table, from the committed
file, is what the configuration says (100,000 subscriptions on 8-level
filters, a tenth with `+` over more than 64 shapes, no client with two
filters matching one topic, 3 to 8 deliveries a message); its traffic file
sizes the warm-up in launches; its four per-layer metrics are data over
readers that were there; a CPU rehearsal of its cell is `correct` and works
the residual NFA engine."""

import collections
import os
import sys
import threading
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from harness import manifest_check, server  # noqa: E402
from harness.reference import Matcher  # noqa: E402
from harness.traffic import Stream, Table  # noqa: E402
from test_correct import rehearse  # noqa: E402
from test_share import SIX, load  # noqa: E402
from test_share_full import read  # noqa: E402

CELL = "plus_100k.sat"
NEW = ["route.shapes_active", "route.residual_filters",
       "route.nfa_matches_per_launch", "route.nfa_flagged_rows"]
MAX_SHAPES = 64  # emqx_tpu/ops/shape_index.py; the benchmark imports no program
CONFIG, TRAFFIC = load("configs", "plus_100k"), load("traffic", CELL)


def shape(flt):
    """What the program's shape index keys a filter by: its length, where
    its `+` sit, whether it ends in `#`."""
    levels = flt.split("/")
    return (len(levels), tuple(i for i, w in enumerate(levels) if w == "+"),
            levels[-1] == "#")


def filters_by_shape(table):
    """-> {shape: its distinct filters}, the exact one among them"""
    out = collections.defaultdict(set)
    for _, _, filters in table.classes():
        for flt in filters:
            out[shape(flt)].add(flt)
    return out


# what the broker's gauge read after the load, by seed (PERF.md section 6)
ON_THE_CHIP = {2147487001: 1_461, 2147487003: 988}


@pytest.mark.parametrize("seed", [7, 2147483901, *ON_THE_CHIP])
def test_the_table_is_what_the_configuration_says(seed):
    table = Table(CONFIG["table"], seed)
    n = table.n_filters()
    assert abs(n - 100_000) <= 1_000 and abs(n - CONFIG["subscriptions"]) <= 1_000
    assert table.n_sub + TRAFFIC["publishers"] == CONFIG["connections"] == 1_048
    by_shape = filters_by_shape(table)
    assert all(length == 8 and not ends_in_hash
               for length, _, ends_in_hash in by_shape)
    wild = {s: f for s, f in by_shape.items() if s[1]}
    assert all(1 <= len(plus) <= 3 for _, plus, _ in wild)
    held = collections.Counter(
        bool(shape(f)[1]) for _, _, fs in table.classes() for f in fs)
    assert abs(held[True] / n - 0.10) <= 0.01
    assert MAX_SHAPES < len(wild) <= 128
    # whatever the order the SUBSCRIBEs arrive in, the index takes 64 shapes
    # and what is left over holds at least 500 filters: at worst the smallest
    sizes = sorted(len(f) for f in by_shape.values())
    assert sum(sizes[:len(sizes) - MAX_SHAPES]) >= 500
    # as the one generator process loads it (subscriber by subscriber, each
    # one's filters in the file's order) the exact shape and the first 63
    # wildcard ones are taken: the residual set is the files' and the seed's
    first = list(dict.fromkeys(
        shape(f) for s in range(table.n_sub) for f in table.filters_of(s)))
    residual = sum(len(by_shape[s]) for s in first[MAX_SHAPES:])
    assert 500 <= residual <= 3_000
    if seed in ON_THE_CHIP:  # gauge route.residual.filters (my chip runs, PR 37)
        assert residual == ON_THE_CHIP[seed]


@pytest.mark.parametrize("seed", [7, 2147483901])
def test_no_client_holds_two_filters_that_match_one_topic(seed):
    spec = CONFIG["table"]
    table = Table(spec, seed)
    matcher = Matcher()
    for cls, _, filters in table.classes():
        for flt in filters:
            matcher.insert(flt, cls)
    template = TRAFFIC["topic"]["template"]
    twice = 0
    for i in range(spec["id_space"]):
        for j in range(spec["j_space"]):  # every topic the traffic can send
            owners = matcher.match(template.format(i=i, j=j))
            twice += len(owners) != len(set(owners))
    assert twice == 0


@pytest.mark.parametrize("seed", [7, 2147483901])
def test_a_message_is_owed_three_to_eight_deliveries(seed):
    table = Table(CONFIG["table"], seed)
    exact, plus = Matcher(), Matcher()
    for cls, _, filters in table.classes():
        for flt in filters:
            (plus if "+" in flt else exact).insert(flt, cls)
    stream = Stream(TRAFFIC, table, seed, 0)
    topics = [stream.topic(k) for k in range(20_000)]
    assert all(len(t.split("/")) == 8 for t in topics)
    n_exact = np.array([len(exact.match(t)) for t in topics])
    n_plus = np.array([len(plus.match(t)) for t in topics])
    assert (n_exact == 1).all()  # its device's own subscriber
    assert 3.0 <= (n_exact + n_plus).mean() <= 8.0
    assert (n_plus > 0).mean() > 0.5
    assert (n_exact + n_plus).max() <= 32  # far from the NFA engine's 64 columns


def test_the_files_are_what_the_issue_names():
    mixed = load("configs", "mixed_1m")
    assert sorted(CONFIG["reduced"]) == ["connections"]
    # mixed_1m's broker, key for key: no setting of the program is tuned to
    # the load generator's pipeline
    assert CONFIG["broker"] == mixed["broker"]
    assert CONFIG["listener_split"] == mixed["listener_split"]
    assert CONFIG["broker"]["session"] == {"max_mqueue": 16384, "max_inflight": 1024}
    assert "no row on the CPU fallback" in CONFIG["guarantees"]["served_by"]
    t = TRAFFIC
    assert (t["loop"], t["publishers"], t["in_flight"], t["qos"]) == ("closed", 48, 100, 1)
    assert (t["payload_bytes"], t["device_share_min"], t["drain_s"]) == (64, 0.5, 60.0)
    assert t["topic"]["i"] == {"draw": "zipf", "a": 1.3, "n": "id_space"}
    assert t["topic"]["j"]["draw"] == "uniform"
    # one process loads the table, a SUBSCRIBE at a time, so that which 64
    # shapes the index takes is decided by the files and --seed alone
    assert t["subscriber_processes"] == 1
    named = {s["bucket"]: s for s in t["warmup"] if "bucket" in s}
    assert set(named) >= {4096, 2048, 1024}
    assert all(s["cold_s"] >= 120 for s in named.values())
    assert t["warmup"][0]["bucket"] == 4096
    assert all(named[b]["seconds"] >= 6.0 for b in (2048, 1024))
    last = t["warmup"][-1]
    assert last["in_flight"] == t["in_flight"] and last["seconds"] >= 20.0
    # between the 1,024 stage and the last one the loop climbs to the
    # window's in-flight count in steps far smaller than a launch and several
    # to a launch's 0.6 s: a jump fixes the loop's cycle of batch sizes by
    # chance, and with it the rate (PERF.md section 6, PR 37)
    stairs = t["warmup"][t["warmup"].index(named[1024]):]
    counts = [s["in_flight"] for s in stairs]
    assert counts == sorted(counts) and counts[-1] == t["in_flight"]
    assert max(b - a for a, b in zip(counts, counts[1:])) <= 2
    assert all(s["seconds"] <= 0.25 for s in stairs[1:-1])
    assert t["settle_s"] == 6.0
    # a run ends well inside 360 s: the warm-up's fixed part and the window
    assert sum(s["seconds"] for s in t["warmup"]) + t["settle_s"] + 30 < 120


def test_the_manifest_holds_the_cell_and_its_metrics():
    manifest, faults = manifest_check.load_and_check(ROOT)
    assert faults == []
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("plus_100k", CELL, 1)
    config = next(c for c in manifest["configs"] if c["name"] == "plus_100k")
    assert config["reduced"] == ["connections"]
    assert config["file"] == "benchmark/configs/plus_100k.json"
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    mine = {e["name"] for e in manifest["per_layer"] if CELL in e.get("workloads", ())}
    one_node = {e["name"] for e in manifest["per_layer"]
                if {"mixed_1m.sat", "fanout_1k.sat"} <= set(e["workloads"])}
    assert mine == one_node | set(NEW)
    by_name = {e["name"]: e for e in manifest["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "deliveries_per_s"
    assert {by_name[n]["layer"] for n in NEW[:2]} == {"router_tables"}
    assert {by_name[n]["layer"] for n in NEW[2:]} == {"device_step"}
    assert by_name["route_step_roofline"]["workloads"][-1] == CELL


def test_the_four_metrics_read_a_scrape():
    prom0 = {"emqx_route_shapes_active": 64, "emqx_route_residual_filters": 812,
             "emqx_route_nfa_matches": 1_000, "emqx_route_nfa_flagged": 0,
             "emqx_ingest_batch_size_count": 50}
    prom1 = {**prom0, "emqx_route_nfa_matches": 31_000, "emqx_route_nfa_flagged": 2,
             "emqx_ingest_batch_size_count": 110}
    ctx = {"prom0": prom0, "prom1": prom1}
    assert [read(name, ctx) for name in NEW] == [64, 812, pytest.approx(500.0), 2]


def test_on_a_program_without_the_series_nothing_raises():
    """The parent has neither gauge nor counter: a gauge has nothing to
    read, the change of a counter that is not there reads 0."""
    plain = {"emqx_messages_received": 1.0, "emqx_ingest_batch_size_count": 3}
    late = {"emqx_messages_received": 9.0, "emqx_ingest_batch_size_count": 8}
    ctx = {"prom0": plain, "prom1": late, "trace": None}
    assert [read(name, ctx) for name in NEW] == [None, None, 0.0, 0.0]


def test_a_rehearsal_of_the_cell_is_correct_and_works_the_nfa_engine():
    by_shape = filters_by_shape(Table(CONFIG["rehearsal_table"], 424242))
    assert len(by_shape) > MAX_SHAPES + 1
    scrapes = []

    def watch(ports):  # the broker's series from the load to its end
        def poll():
            while True:
                try:
                    scrapes.append(server.scrape(ports["rest"]))
                except OSError:
                    return
                time.sleep(0.5)
        threading.Thread(target=poll, daemon=True).start()
    r = rehearse(CELL, {"loaded": watch})
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["chip_run"] is False and r["metrics"] == {}
    assert list(r["checks"]) == SIX
    assert r["counts"]["broker_faults"] == {}
    ctx = {"prom0": scrapes[0], "prom1": scrapes[-1]}
    shapes, residual, per_launch, flagged = (read(name, ctx) for name in NEW)
    assert shapes == MAX_SHAPES and residual > 0
    assert per_launch > 0 and flagged == 0
