"""Of the seconds in the trace's longest idle gaps (harness/trace_reduce.py
`idle_gaps`: at most ten, each labelled by the shortest host event that
covers half of it), the share whose label starts with `prefix`: how much of
the device's idle time the program's own annotations name. No trace, or no
gap in it: nothing to read."""


def read(args, ctx):
    gaps = (ctx.get("trace") or {}).get("idle_gaps")
    total = sum(seconds for _, seconds in gaps or ())
    if not total:
        return None
    named = sum(seconds for label, seconds in gaps
                if label.startswith(args["prefix"]))
    return named / total * 100.0
