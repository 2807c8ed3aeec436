"""A number from the device trace's reduction (harness/trace_reduce.py):
`idle_share`, or `program_ms`: device seconds of the programs whose name
contains `match`, per launch of them, in ms. No trace, or no such program in
it: nothing to read."""


def programs(ctx, match):
    trace = ctx.get("trace")
    if not trace:
        return None
    hit = [p for name, p in trace["programs"].items() if match in name]
    count = sum(p["count"] for p in hit)
    if not count:
        return None
    return sum(p["seconds"] for p in hit), count


def read(args, ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    if args["field"] == "idle_share":
        v = trace.get("idle_share")
        return None if v is None else v * 100.0
    if args["field"] == "program_ms":
        got = programs(ctx, args["match"])
        return None if got is None else got[0] / got[1] * 1e3
    raise ValueError(f"unknown trace field {args['field']!r}")
