"""A named reader applied to every node of the deployment on its own (the
entries of `ctx["nodes"]`: one node's `prom0`, `prom1`, `proc`, `trace`), and
the nodes' values reduced to one: `max`, `min`, `mean`, or `max_share` (the
largest node's share of the nodes' sum: the skew), times `scale`. With `over`,
a second reader's value divides the first's node by node (a total over a
count where both only move during set-up, so that no window's change reads
them). A node on which a reader finds nothing is left out; no node with a
value, or a context without nodes (the parent's): nothing to read."""

import importlib


def one(spec, node):
    reader = importlib.import_module("readers." + spec["reader"])
    return reader.read(spec["args"], node)


def read(args, ctx):
    values = []
    for node in ctx.get("nodes") or ():
        v = one(args, node)
        if v is not None and "over" in args:
            den = one(args["over"], node)
            v = v / den if den else None
        if v is not None:
            values.append(v)
    if not values:
        return None
    how = args["reduce"]
    if how == "max_share":
        total = sum(values)
        if total <= 0:
            return None
        out = max(values) / total
    elif how == "mean":
        out = sum(values) / len(values)
    elif how in ("max", "min"):
        out = (max if how == "max" else min)(values)
    else:
        raise ValueError(f"unknown reduction {how!r}")
    return out * args.get("scale", 1.0)
