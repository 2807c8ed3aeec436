"""BENCHMARK.json checked against its contract before anything is started.
A manifest error costs a whole PR (PR 22 was refused for a `layer` with a
space in it), so run.py calls `check` first and a test holds every rule.
`check(manifest, root)` returns a list of faults; empty means sound."""

import json
import os
import re
import sys

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
MAX_EXTRA_END_TO_END = 4  # besides setup_s (ISSUE 23)
REDUCED_WIDTH = re.compile(
    r"(_dim|_rank)$|hidden|intermediate|latent|state_size|proj|head_size"
    r"|expansion|experts_per_tok")


def _line(s, lo=1, hi=200):
    return isinstance(s, str) and lo <= len(s) <= hi \
        and "\n" not in s and "\t" not in s


def _keys(entry, required, optional, what, faults):
    if not isinstance(entry, dict):
        faults.append(f"{what}: not an object")
        return False
    extra = set(entry) - set(required) - set(optional)
    lack = set(required) - set(entry)
    if extra:
        faults.append(f"{what}: keys not allowed: {sorted(extra)}")
    if lack:
        faults.append(f"{what}: keys missing: {sorted(lack)}")
    return not lack


def check(manifest, root):
    faults = []
    if not isinstance(manifest, dict) or set(manifest) != TOP_KEYS:
        return [f"top level must have exactly the keys {sorted(TOP_KEYS)}"]
    m = manifest

    paths = m["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        faults.append("paths: 1 to 16 directories")
        paths = []
    for p in paths:
        if not (isinstance(p, str) and PATH.match(p)) or p.startswith("/") \
                or ".." in p.split("/"):
            faults.append(f"paths: bad directory {p!r}")
    cmd = m["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32
            and all(_line(w) for w in cmd)):
        faults.append("command: a list of 1 to 32 one-line strings")
    else:
        for w in cmd:
            if w.startswith("/") or ".." in w.split("/"):
                faults.append(f"command: {w!r} leaves the repo")
            elif "/" in w and not any(
                    w == p or w.startswith(p.rstrip("/") + "/") for p in paths):
                faults.append(f"command: {w!r} is a file outside `paths`")
    rs = m["run_seconds"]
    if not (isinstance(rs, int) and not isinstance(rs, bool) and 10 <= rs <= 51):
        faults.append("run_seconds: one whole number from 10 to 51")

    def under_paths(f):
        return isinstance(f, str) and PATH.match(f) and any(
            f.startswith(p.rstrip("/") + "/") for p in paths)

    configs = {}
    if not (isinstance(m["configs"], list) and 1 <= len(m["configs"]) <= 24):
        faults.append("configs: 1 to 24 entries")
    files = set()
    for c in m["configs"] if isinstance(m["configs"], list) else []:
        if not _keys(c, ("name", "source", "file", "reduced", "why"), (),
                     f"config {c.get('name') if isinstance(c, dict) else c!r}",
                     faults):
            continue
        what = f"config {c['name']!r}"
        if not (isinstance(c["name"], str) and NAME.match(c["name"])):
            faults.append(f"{what}: name is not an identifier")
        if c["name"] in configs:
            faults.append(f"{what}: name used twice")
        configs[c["name"]] = c
        if not _line(c["source"]) or not _line(c["why"]):
            faults.append(f"{what}: source and why are 1 to 200 characters on one line")
        if not under_paths(c["file"]):
            faults.append(f"{what}: file {c['file']!r} is not under `paths`")
        elif not os.path.isfile(os.path.join(root, c["file"])):
            faults.append(f"{what}: file {c['file']!r} does not exist")
        if c["file"] in files:
            faults.append(f"{what}: file shared with another configuration")
        files.add(c["file"])
        red = c["reduced"]
        if not (isinstance(red, list) and len(red) <= 16 and all(
                isinstance(k, str) and NAME.match(k) for k in red)):
            faults.append(f"{what}: reduced is at most 16 identifiers")
        else:
            for k in red:
                if REDUCED_WIDTH.search(k):
                    faults.append(f"{what}: reduced may not name a width ({k})")

    cells, pairs, used = {}, set(), set()
    if not (isinstance(m["workloads"], list) and 1 <= len(m["workloads"]) <= 24):
        faults.append("workloads: 1 to 24 cells")
    for w in m["workloads"] if isinstance(m["workloads"], list) else []:
        if not _keys(w, ("name", "config", "traffic", "chips", "why"), (),
                     f"cell {w.get('name') if isinstance(w, dict) else w!r}",
                     faults):
            continue
        what = f"cell {w['name']!r}"
        for k in ("name", "config", "traffic"):
            if not (isinstance(w[k], str) and NAME.match(w[k])):
                faults.append(f"{what}: {k} is not an identifier")
        if w["name"] in cells:
            faults.append(f"{what}: name used twice")
        cells[w["name"]] = w
        if w["config"] not in configs:
            faults.append(f"{what}: unknown config {w['config']!r}")
        used.add(w["config"])
        if (w["config"], w["traffic"]) in pairs:
            faults.append(f"{what}: config and traffic pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4) or isinstance(w["chips"], bool):
            faults.append(f"{what}: chips is 1 or 4")
        if not _line(w["why"]):
            faults.append(f"{what}: why is 1 to 200 characters on one line")
        if isinstance(w["traffic"], str) and paths and not any(
                os.path.isfile(os.path.join(root, p, "traffic", w["traffic"] + sfx))
                for p in paths for sfx in TRAFFIC_SUFFIXES):
            faults.append(f"{what}: no traffic file {w['traffic']!r}")
    for name in set(configs) - used:
        faults.append(f"config {name!r}: used by no cell")
    four = sum(1 for w in cells.values() if w.get("chips") == 4)
    if four > max(1, len(cells) // 2):
        faults.append("workloads: too many four-chip cells")

    def metric_cells(e):
        return list(cells) if "workloads" not in e else e["workloads"]

    names, e2e = set(), {}

    def metric(e, what, required):
        if not _keys(e, required, ("workloads",), what, faults):
            return False
        if not (isinstance(e["name"], str) and NAME.match(e["name"])):
            faults.append(f"{what}: name is not an identifier")
        if e["name"] in names:
            faults.append(f"{what}: name used twice")
        names.add(e["name"])
        if not (isinstance(e["unit"], str) and UNIT.match(e["unit"])):
            faults.append(f"{what}: unit {e['unit']!r}: 1 to 16 of letters, "
                          "digits, _ / % . -")
        if e["better"] not in ("lower", "higher"):
            faults.append(f"{what}: better is lower or higher")
        if e["source"] not in SOURCES:
            faults.append(f"{what}: source is one of {sorted(SOURCES)}")
        if "workloads" in e:
            wl = e["workloads"]
            if not (isinstance(wl, list) and wl and all(c in cells for c in wl)
                    and len(set(wl)) == len(wl)):
                faults.append(f"{what}: workloads must list known cells, once each")
                return False
        elif "layer" in e:  # an end-to-end metric without one is every cell's
            faults.append(f"{what}: needs an explicit workloads list")
        return True

    if not (isinstance(m["end_to_end"], list) and 1 <= len(m["end_to_end"]) <= 16):
        faults.append("end_to_end: 1 to 16 metrics")
    for e in m["end_to_end"] if isinstance(m["end_to_end"], list) else []:
        what = f"end_to_end metric {e.get('name') if isinstance(e, dict) else e!r}"
        if not metric(e, what, ("name", "unit", "better", "bound", "source")):
            continue
        e2e[e["name"]] = e
        if e["source"] not in ("host_clock", "device_trace"):
            faults.append(f"{what}: source is host_clock or device_trace")
        b = e["bound"]
        if not (isinstance(b, (int, float)) and not isinstance(b, bool)
                and 0.01 <= b <= 0.25):
            faults.append(f"{what}: bound is from 0.01 to 0.25")
    if "setup_s" not in e2e:
        faults.append("end_to_end: setup_s is required")
    elif "workloads" in e2e["setup_s"]:
        faults.append("end_to_end metric 'setup_s': every cell reports it; no workloads list")
    if len(e2e) - ("setup_s" in e2e) > MAX_EXTRA_END_TO_END:
        faults.append(f"end_to_end: at most {MAX_EXTRA_END_TO_END} besides setup_s")

    if not (isinstance(m["per_layer"], list) and 1 <= len(m["per_layer"]) <= 128):
        faults.append("per_layer: 1 to 128 metrics")
    layered = set()
    for e in m["per_layer"] if isinstance(m["per_layer"], list) else []:
        what = f"per_layer metric {e.get('name') if isinstance(e, dict) else e!r}"
        if not metric(e, what, ("name", "unit", "better", "source", "layer", "moves")):
            continue
        if not (isinstance(e["layer"], str) and NAME.match(e["layer"])):
            faults.append(
                f"{what}: layer must be 1 to 64 characters from letters, "
                f"digits, '_', '.' and '-', not {e['layer']!r}")
        target = e2e.get(e["moves"])
        if target is None:
            faults.append(f"{what}: moves {e['moves']!r} is no end_to_end metric")
        else:
            for c in metric_cells(e):
                if c not in metric_cells(target):
                    faults.append(
                        f"{what}: cell {c!r} does not report {e['moves']!r}")
        if e["name"].endswith("_roofline") and e["unit"] != "%":
            faults.append(f"{what}: a roofline share has the unit %")
        for p in paths:
            if not os.path.isfile(os.path.join(root, p, "metrics", e["name"] + ".json")):
                faults.append(f"{what}: no reader file metrics/{e['name']}.json")
        layered.update(metric_cells(e))
    for c, w in cells.items():
        mine = [e for e in e2e.values() if c in metric_cells(e)]
        if len(mine) < 2:
            faults.append(f"cell {c!r}: reports no end_to_end metric besides setup_s")
        if c not in layered:
            faults.append(f"cell {c!r}: reports no per_layer metric")
    if len(json.dumps(manifest)) > 64 * 1024:
        faults.append("the manifest is larger than 64 KiB")
    return faults


def load_and_check(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    return manifest, check(manifest, root)


if __name__ == "__main__":
    _, found = load_and_check(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    for fault in found:
        print(fault)
    sys.exit(1 if found else 0)
