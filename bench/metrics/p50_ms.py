"""Median due-to-answer latency over every request of the window (ms);
a failed or shed request counts as never answered."""
from bench.traffic import percentile


def read(ctx):
    if ctx.traffic["loop"] != "open":
        return None
    return percentile(ctx.window.latency_ms(), 50)
