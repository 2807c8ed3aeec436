"""A number the load generator's own processes took of themselves."""


def read(args, ctx):
    v = (ctx.get("loadgen") or {}).get(args["field"])
    return None if v is None else v * args.get("scale", 1.0)
