"""A gauge's value at the window's end (the second scrape), times `scale`:
a value, not a change. The program exports no such series: nothing to read."""


def read(args, ctx):
    v = (ctx.get("prom1") or {}).get(args["series"])
    return None if v is None else v * args.get("scale", 1.0)
