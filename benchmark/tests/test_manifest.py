"""BENCHMARK.json as committed is sound, and manifest_check refuses each
fault that would cost a PR."""

import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from harness import manifest_check  # noqa: E402


@pytest.fixture()
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_committed_manifest_is_sound(manifest):
    assert manifest_check.check(manifest, ROOT) == []


def test_every_name_and_layer_is_an_identifier(manifest):
    names = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for e in manifest[k]] + [e["layer"] for e in manifest["per_layer"]]
    for n in names:
        assert manifest_check.NAME.match(n), n
    for e in manifest["per_layer"]:
        assert e["workloads"], e["name"]


def _per_layer(m, name):
    return next(e for e in m["per_layer"] if e["name"] == name)


def _e2e(m, name):
    return next(e for e in m["end_to_end"] if e["name"] == name)


CASES = {
    "layer_with_a_space": lambda m: _per_layer(m, "owner.cpu_share").update(
        layer="owner process"),  # the rule PR 22 broke
    "layer_with_a_slash": lambda m: _per_layer(m, "owner.cpu_share").update(
        layer="ingest/slo"),
    "unit_of_17_characters": lambda m: _per_layer(m, "owner.cpu_share").update(
        unit="percent_of_a_core"),
    "unit_with_a_space": lambda m: _e2e(m, "deliveries_per_s").update(
        unit="dlv per s"),
    "moves_a_metric_the_cell_lacks": lambda m: _e2e(
        m, "deliveries_per_s").update(workloads=["mixed_1m.sat"]),
    "end_to_end_metric_in_no_known_cell": lambda m: _e2e(
        m, "deliveries_per_s").update(workloads=["mixed_1m.paced"]),
    "moves_no_metric": lambda m: _per_layer(m, "owner.cpu_share").update(
        moves="msgs_per_s"),
    "name_with_a_plus": lambda m: _per_layer(m, "owner.cpu_share").update(
        name="owner+workers"),
    "metric_without_workloads": lambda m: _per_layer(
        m, "owner.cpu_share").pop("workloads"),
    "metric_with_a_why": lambda m: _per_layer(m, "owner.cpu_share").update(
        why="because"),
    "chips_of_two": lambda m: m["workloads"][0].update(chips=2),
    "run_seconds_over_51": lambda m: m.update(run_seconds=52),
    "run_seconds_fractional": lambda m: m.update(run_seconds=30.5),
    "bound_over_a_quarter": lambda m: _e2e(m, "deliveries_per_s").update(bound=0.3),
    "no_setup_s": lambda m: m["end_to_end"].pop(0),
    "five_end_to_end_besides_setup_s": lambda m: m["end_to_end"].extend(
        dict(_e2e(m, "deliveries_per_s"), name=f"extra_{i}") for i in range(4)),
    "cell_without_a_traffic_file": lambda m: m["workloads"][0].update(
        traffic="no_such_mix"),
    "cell_of_an_unknown_config": lambda m: m["workloads"][0].update(
        config="mixed_2m"),
    "config_file_outside_paths": lambda m: m["configs"][0].update(
        file="emqx_tpu/config/schema.py"),
    "source_of_201_characters": lambda m: m["configs"][0].update(source="x" * 201),
    "why_on_two_lines": lambda m: m["workloads"][0].update(why="a\nb"),
    "reduced_names_a_width": lambda m: m["configs"][0].update(
        reduced=["hidden_size"]),
    "duplicate_cell": lambda m: m["workloads"].append(dict(m["workloads"][0])),
    "command_outside_paths": lambda m: m.update(command=["python3", "bench.py/x"]),
    "extra_top_level_key": lambda m: m.update(notes="x"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_fault_is_refused(manifest, case):
    broken = copy.deepcopy(manifest)
    CASES[case](broken)
    assert manifest_check.check(broken, ROOT), case
