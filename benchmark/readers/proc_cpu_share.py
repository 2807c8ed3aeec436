"""CPU seconds of the server's processes over the window, of one core, from
/proc/<pid>/stat at the window's two edges: `who` is `owner` (the process
that holds the chip) or `workers` (the listener pool; the busiest one)."""


def read(args, ctx):
    shares = (ctx.get("proc") or {}).get(args["who"])
    if not shares:
        return None
    return max(shares) * 100.0
