"""The route step's share of its roofline: the least time the chip could take
for the work of one launch of this cell's traffic (harness/roofline.py: rows
per launch from the program's counters, topic bytes and matches per message
from the traffic and the reference), over the device time per launch from
the trace. Never 0 and never capped: no trace, nothing to read."""

from harness import roofline
from readers import prom_delta_ratio, trace_reduce


def read(args, ctx):
    got = trace_reduce.programs(ctx, args["match"])
    rows = prom_delta_ratio.read(
        {"num": [args["rows"]], "den": [args["launches"] + "_count"]}, ctx)
    work = ctx.get("work")
    if got is None or not rows or not work or not got[0]:
        return None
    least, _ = roofline.least_seconds(
        roofline.route_step_work(rows, work["topic_bytes_mean"],
                                 work["fan_mean"]), ctx["peaks"])
    return least / (got[0] / got[1]) * 100.0
