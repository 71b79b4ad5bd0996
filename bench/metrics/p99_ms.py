"""99th percentile of the due-to-answer latency over every request of
the window (ms, nearest rank); a failed or shed request misses."""
from bench.traffic import percentile


def read(ctx):
    if ctx.traffic["loop"] != "open":
        return None
    return percentile(ctx.window.latency_ms(), 99)
