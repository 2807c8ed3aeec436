"""Change of one or more Prometheus series over the window, over the change
of others (or alone, without `den`), times `scale`. Nothing moved in the
denominator: nothing to read."""


def delta(ctx, names):
    return sum(ctx["prom1"].get(n, 0.0) - ctx["prom0"].get(n, 0.0)
               for n in names)


def read(args, ctx):
    if ctx.get("prom0") is None or ctx.get("prom1") is None:
        return None
    num = delta(ctx, args["num"])
    if "den" not in args:
        return num * args.get("scale", 1.0)
    den = delta(ctx, args["den"])
    return num / den * args.get("scale", 1.0) if den > 0 else None
