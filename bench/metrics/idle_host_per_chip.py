"""Share of the traced window in which a chip ran nothing while its own
cell's batch worker held it back — the worker anywhere but ``collect``,
or a full garbage collection open on any thread — averaged over the
chips the cells drive.  With one cell on one chip this is
``idle_host``; with several cells each chip is paired with its own
worker through the ``device`` its cell's spans carry
(``bench.chiptrace``), so a worker busy while another cell's chip idles
does not count there.

``describe`` prints, per chip, its share, its idle time by its worker's
state, and how many of its programs start inside its worker's
``dispatch`` spans (the check on the two clocks)."""
import numpy as np

from bench import chiptrace, progtrace


def per_chip(ctx):
    """chip id -> ``progtrace._attribute`` of that chip's ops against its
    own workers; None where the spans carry no ``device``.  Cached on
    ``ctx``."""
    if hasattr(ctx, "idle_host_per_chip"):
        return ctx.idle_host_per_chip
    ctx.idle_host_per_chip = out = None
    lay = chiptrace.layout(ctx)
    if lay is not None:
        pl, win, off, workers = lay
        ops = chiptrace.chip_lines(pl, "XLA Ops")
        out = {chip: progtrace._attribute(ctx, pl, win, ops[chip], tids,
                                          off)
               for chip, tids in sorted(workers.items()) if chip in ops}
        ctx.idle_host_per_chip = out = out or None
    return out


def _share(att) -> float:
    return (att["idle_s"] - att["split_s"]["collect"]) / att["window_s"]


def read(ctx):
    att = per_chip(ctx)
    if not att or any(a["window_s"] <= 0 for a in att.values()):
        return None
    return float(np.mean([_share(a) for a in att.values()]))


def describe(ctx):
    att = per_chip(ctx)
    if not att:
        return None
    pl, win, off, workers = chiptrace.layout(ctx)
    modules = chiptrace.chip_lines(pl, "XLA Modules")
    parts = []
    for chip, a in att.items():
        disp = chiptrace.intervals(ctx, "dispatch", workers[chip], off[0])
        starts = [s for _, s, _ in modules.get(chip, [])
                  if win[0] <= s < win[1]]
        inside = chiptrace.starts_inside(starts, disp)
        states = ", ".join(f"{s} {v!r}" for s, v in a["split_s"].items())
        parts.append(f"chip {chip}: {_share(a)!r} ({a['idle_s']!r} s idle "
                     f"of {a['window_s']!r} s: {states}; programs starting "
                     f"inside its worker's dispatch: {inside} of "
                     f"{len(starts)})")
    return "idle held by each chip's own worker: " + "; ".join(parts)
