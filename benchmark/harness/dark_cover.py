"""The program's dark intervals on the trace's clock. emqx_tpu's profiler
writes `dark.json` beside a capture's .xplane.pb: the intervals in which no
launch was lit, each split by cause, on `perf_counter_ns`, and the clock
anchor (an `emqx:clock` annotation entered at arm with the `perf_counter_ns`
read at that moment). Mapped through the anchor, they are set against the
trace's ten longest idle gaps: for each gap the program's largest cause, and
the share of the gaps' seconds that program intervals cover. Run as a child
with JAX_PLATFORMS=cpu, like trace_reduce.py:

    python dark_cover.py <file.xplane.pb> [dark.json]  -> one JSON object

(`dark.json` beside the trace by default). A capture without the anchor or
without `dark.json`, or a trace without a gap: null."""

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from trace_reduce import OPS_LINE, load, union_seconds  # noqa: E402

ANCHOR = "emqx:clock"


def longest_gaps(planes, n=10):
    """The `n` longest idle gaps [(start_ns, end_ns)] of the device planes,
    as trace_reduce.reduce_planes finds them (its `idle_gaps` gives their
    lengths only)."""
    device = [ls for name, ls in planes if re.match(r"^/device:TPU:\d+$", name)]
    if not device:
        return []
    lo = min(ev[0][1] for _, ls in planes for _, ev in ls if ev)
    hi = max(max(s + d for _, s, d in ev[-64:]) for _, ls in planes for _, ev in ls if ev)
    for name, lines in planes:
        if name.startswith("/host:"):
            for _, events in lines:
                for ev, start, dur in events:
                    if ev.endswith(" start_trace"):
                        lo = max(lo, start + dur)
                    elif ev.endswith(" stop_trace"):
                        hi = min(hi, start)
    gaps = []
    for lines in device:
        names = [ln for ln, _ in lines]
        use = [OPS_LINE] if OPS_LINE in names else [ln for ln in names if ln != "Steps"]
        intervals = [(max(s, lo), min(s + d, hi)) for ln, events in lines if ln in use
                     for _, s, d in events if s + d > lo and s < hi]
        _, plane_gaps = union_seconds(intervals)
        if intervals:
            plane_gaps += [(lo, min(s for s, _ in intervals)),
                           (max(e for _, e in intervals), hi)]
        else:
            plane_gaps = [(lo, hi)]
        gaps += plane_gaps
    return sorted(gaps, key=lambda g: g[0] - g[1])[:n]


def anchor_offset(planes, dark):
    """Trace ns less perf_counter ns: where the anchor annotation starts on
    the trace, less the `perf_counter_ns` read when it was entered."""
    for name, lines in planes:
        if name.startswith("/host:"):
            for _, events in lines:
                for ev, start, _ in events:
                    if ev.startswith(ANCHOR):
                        return start - dark["clock"]["perf_counter_ns"]
    return None


def dark_cover(gaps, planes, dark):
    """gaps: [(start_ns, end_ns)] on the trace's clock. -> {"gaps": [[the
    program's largest cause, seconds, share covered]], "covered_share": % of
    the gaps' seconds inside program intervals}. A gap's causes are those of
    the intervals over it, each weighted by the share of the interval the gap
    overlaps. None without an anchor or a gap."""
    total = sum(e - s for s, e in gaps)
    off = anchor_offset(planes, dark) if dark else None
    if off is None or not total:
        return None
    ivs = [(iv["start_ns"] + off, iv["end_ns"] + off, iv["causes"])
           for iv in dark["intervals"] if iv["end_ns"] > iv["start_ns"]]
    rows, covered = [], 0
    for gs, ge in gaps:
        by, cov = {}, 0
        for s, e, causes in ivs:  # the program's intervals never overlap
            o = min(ge, e) - max(gs, s)
            if o > 0:
                cov += o
                for cause, seconds in causes.items():
                    by[cause] = by.get(cause, 0.0) + seconds * o / (e - s)
        covered += cov
        rows.append([max(by, key=by.get) if by else "uncovered", (ge - gs) / 1e9,
                     cov / (ge - gs)])
    return {"gaps": rows, "covered_share": covered / total * 100.0}


def main(argv):
    path = argv[1]
    dark_path = argv[2] if len(argv) > 2 else os.path.join(os.path.dirname(path), "dark.json")
    dark = None
    if os.path.isfile(dark_path):
        with open(dark_path) as f:
            dark = json.load(f)
    planes = load(path)
    return dark_cover(longest_gaps(planes), planes, dark)


if __name__ == "__main__":
    print(json.dumps(main(sys.argv)))
