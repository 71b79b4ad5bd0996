"""99th percentile of how late the open-loop generator sent each request
(send time minus due time, ms): a starved generator is not a fast
server."""
from bench.traffic import percentile


def read(ctx):
    if ctx.traffic["loop"] != "open":
        return None
    w = ctx.window
    return percentile((w.sent - w.due) * 1e3, 99)
