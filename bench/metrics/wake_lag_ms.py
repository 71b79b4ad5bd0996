"""Median, over the traced window's dispatches, of how long a finished
result waited for its worker (ms): the end of the dispatch's
``backend.wait`` span minus the end of the last program on its cell's
chip that started inside the dispatch before that wait ended.  The
chip is done at the program's end; the worker, blocked in
``block_until_ready``, returns when it gets the interpreter lock back.
The cell's chip is the ``device`` its ``dispatch`` span carries
(``bench.chiptrace``); a program whose spans carry none reads None.

``describe`` prints the p50, p90 and p99 of each chip's lags."""
import bisect
from collections import defaultdict

import numpy as np

from bench import chiptrace, progtrace


def lags(ctx):
    """chip ids (a tuple) -> the window's wake lags of dispatches on
    them, in ms; None where the spans carry no ``device``.  Cached on
    ``ctx``."""
    if hasattr(ctx, "wake_lags"):
        return ctx.wake_lags
    ctx.wake_lags = None
    lay = chiptrace.layout(ctx)
    if lay is None:
        return None
    pl, (lo, hi), (shift, _, _), _ = lay
    programs = {}
    for chip, evs in chiptrace.chip_lines(pl, "XLA Modules").items():
        prog = sorted((s, s + d) for _, s, d in evs)
        programs[chip] = ([s for s, _ in prog], [e for _, e in prog])
    waits = defaultdict(list)
    for ev in progtrace.spans(ctx, "backend.wait"):
        waits[ev["tid"]].append((ev["ts"] + ev["dur"]) * 1e3 + shift)
    for w in waits.values():
        w.sort()
    out = defaultdict(list)
    for ev in progtrace.spans(ctx, "dispatch"):
        chips = chiptrace.chips_of(ev["args"].get("device"))
        a = ev["ts"] * 1e3 + shift
        b = a + ev["dur"] * 1e3
        w = waits.get(ev["tid"], [])
        j = bisect.bisect_right(w, b) - 1
        if not chips or a < lo or b > hi or j < 0 or w[j] < a:
            continue
        done = [max(ends[bisect.bisect_left(starts, a):
                         bisect.bisect_right(starts, w[j])], default=None)
                for starts, ends in (programs.get(c, ([], []))
                                     for c in chips)]
        done = [t for t in done if t is not None]
        if done:
            out[chips].append((w[j] - max(done)) / 1e6)
    ctx.wake_lags = dict(out) or None
    return ctx.wake_lags


def read(ctx):
    per = lags(ctx)
    if not per:
        return None
    return float(np.median([v for vs in per.values() for v in vs]))


def describe(ctx):
    per = lags(ctx)
    if not per:
        return None
    parts = []
    for chips, vs in sorted(per.items()):
        p = [float(v) for v in np.percentile(vs, [50, 90, 99])]
        parts.append(f"chip {','.join(map(str, chips))}: {len(vs)} "
                     f"dispatches, p50 {p[0]!r} ms, p90 {p[1]!r} ms, p99 "
                     f"{p[2]!r} ms")
    return "wake lag, chip done to worker back: " + "; ".join(parts)
