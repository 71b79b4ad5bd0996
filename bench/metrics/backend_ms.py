"""Median of the program tracer's raw ``kernel`` spans (ms): one
``ShardedSearchBackend`` call on the host clock — lock wait, query
placement and device execution up to ``block_until_ready``."""
import numpy as np


def read(ctx):
    if not ctx.spans:
        return None
    durs = [ev["dur"] for ev in ctx.spans
            if ev.get("ph") == "X" and ev["name"] == "kernel"]
    if not durs:
        return None
    return float(np.median(durs)) / 1e3
