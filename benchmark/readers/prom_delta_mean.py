"""Mean of a histogram's observations inside the window: the change of the
`_sum` of each series in `series`, over the change of the `_count` of
`count_of` (the first series unless given), times `scale`."""

from readers import prom_delta_ratio


def read(args, ctx):
    count_of = args.get("count_of", args["series"][0])
    return prom_delta_ratio.read({
        "num": [s + "_sum" for s in args["series"]],
        "den": [count_of + "_count"], "scale": args.get("scale", 1.0)}, ctx)
