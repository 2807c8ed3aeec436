"""From a profiler trace (.xplane.pb) to device busy and idle seconds, time
per device program, the device operations that took most time, and the
longest idle gaps labelled by what the host was doing. Run as a child with
JAX_PLATFORMS=cpu once the server has exited, so that the driver never holds
the chip:  python trace_reduce.py <file.xplane.pb>  -> one JSON object."""

import json
import re
import sys

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_EVENT_CAP = 6_000_000  # python-tracer lines can be huge


def union_seconds(intervals):
    """intervals: [(start_ns, end_ns)] -> (busy seconds, gaps [(start, end)])."""
    busy, gaps, cur_s, cur_e = 0, [], None, None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            busy += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy / 1e9, gaps


def program_name(event_name):
    """`jit_route_step(1234567)` -> `jit_route_step`."""
    return re.sub(r"\(\d+\)$", "", event_name)


def reduce_planes(planes):
    """planes: [(plane name, [(line name, [(event name, start_ns, dur_ns)])])].
    Device planes are `/device:TPU:<n>`; busy is the union of the intervals of
    their `XLA Ops` line (every line but `Steps` where a plane has no such
    line), averaged over the device planes."""
    device = [(n, ls) for n, ls in planes
              if re.match(r"^/device:TPU:\d+$", n)]
    if not device:
        return None
    lo, hi = None, None
    for _, lines in planes:
        for _, events in lines:
            if events:
                lo = events[0][1] if lo is None else min(lo, events[0][1])
                end = max(s + d for _, s, d in events[-64:])
                hi = end if hi is None else max(hi, end)
    # the capture's own start and stop (seconds of export with the python
    # tracer on) are inside the trace: the window is what lies between them
    for name, lines in planes:
        if name.startswith("/host:"):
            for _, events in lines:
                for ev, start, dur in events:
                    if ev.endswith(" start_trace"):
                        lo = max(lo, start + dur)
                    elif ev.endswith(" stop_trace"):
                        hi = min(hi, start)
    window = (hi - lo) / 1e9
    busy_s, programs, ops, gaps = [], {}, {}, []
    for _, lines in device:
        names = [ln for ln, _ in lines]
        use = [OPS_LINE] if OPS_LINE in names else \
            [ln for ln in names if ln != "Steps"]
        intervals = []
        for ln, events in lines:
            if ln in use:
                intervals += [(s, s + d) for _, s, d in events]
            if ln == OPS_LINE:
                for name, _, d in events:
                    ops[name] = ops.get(name, 0.0) + d / 1e9
            if ln == MODULES_LINE:
                for name, _, d in events:
                    p = programs.setdefault(
                        program_name(name), {"seconds": 0.0, "count": 0})
                    p["seconds"] += d / 1e9
                    p["count"] += 1
        intervals = [(max(a, lo), min(b, hi)) for a, b in intervals
                     if b > lo and a < hi]
        busy, plane_gaps = union_seconds(intervals)
        if intervals:
            first = min(s for s, _ in intervals)
            last = max(e for _, e in intervals)
            plane_gaps += [(lo, first), (last, hi)]
        else:
            plane_gaps = [(lo, hi)]
        busy_s.append(busy)
        gaps += plane_gaps
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    labels = label_gaps(gaps, [(n, ls) for n, ls in planes
                               if n.startswith("/host:")])
    n = len(device)
    return {
        "devices": n, "window_s": window, "busy_s": sum(busy_s) / n,
        "idle_share": 1.0 - (sum(busy_s) / n) / window if window > 0 else None,
        "programs": {k: {"seconds": v["seconds"] / n, "count": v["count"] / n}
                     for k, v in programs.items()},
        "device_ops": [[k[:120], v / n] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[lab[:120], (e - s) / 1e9]
                      for lab, (s, e) in zip(labels, gaps)],
    }


def label_gaps(gaps, host_planes):
    """Each gap takes the name of the shortest host event that covers at
    least half of it (the most specific thing the host was doing), else
    `unattributed`."""
    best = [None] * len(gaps)
    floor = min((e - s for s, e in gaps), default=0) / 2
    for _, lines in host_planes:
        for _, events in lines:
            for name, s, d in events:
                if d < floor:
                    continue
                for i, (gs, ge) in enumerate(gaps):
                    cover = min(ge, s + d) - max(gs, s)
                    if cover * 2 >= ge - gs and (
                            best[i] is None or d < best[i][0]):
                        best[i] = (d, name)
    return [b[1] if b else "unattributed" for b in best]


def load(path):
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        lines, host = [], plane.name.startswith("/host:")
        for line in plane.lines:
            events = []
            for e in line.events:
                events.append((e.name, int(e.start_ns), int(e.duration_ns)))
                if host and len(events) >= HOST_EVENT_CAP:
                    break
            lines.append((line.name, events))
        out.append((plane.name, lines))
    return out


if __name__ == "__main__":
    print(json.dumps(reduce_planes(load(sys.argv[1]))))
