"""The program's spans laid over the device trace chip by chip.

A serving cell tags its worker's ``collect`` and ``dispatch`` spans with
``device``: the id of the chip its backend's mesh holds, or the ids where
the mesh holds several (``repro.serve.cell``).  The device plane
``/device:<PLATFORM>:<n>`` is chip ``n``.  So each chip can be paired
with the one worker that drives it, where ``bench.progtrace.attribute``
lays the first chip over every worker at once.  A program whose spans
carry no ``device`` pairs nothing, and the readers built on this module
read None.
"""
from __future__ import annotations

from collections import defaultdict

from bench import devtrace, progtrace


def chips_of(device) -> tuple:
    """The chip ids of a span's ``device`` attribute (an id, a list of
    ids, or None)."""
    if device is None:
        return ()
    if isinstance(device, int):
        return (device,)
    return tuple(int(c) for c in device)


def chip_workers(ctx) -> dict:
    """chip id -> the thread ids of the workers whose cell drives it,
    from the ``device`` of their ``collect`` spans."""
    out = defaultdict(set)
    for ev in progtrace.spans(ctx, "collect"):
        for chip in chips_of(ev["args"].get("device")):
            out[chip].add(ev["tid"])
    return dict(out)


def chip_lines(planes, line: str) -> dict:
    """chip id -> that chip's events on ``line`` (its ``XLA Modules``
    where the plane has no such line)."""
    out = {}
    for name, lines in planes:
        if devtrace._DEVICE.match(name):
            by = dict(lines)
            out[int(name.rsplit(":", 1)[1])] = (by.get(line)
                                                or by.get("XLA Modules")
                                                or [])
    return out


def layout(ctx):
    """(planes, window, offset, chip -> worker tids) of a traced run
    whose spans carry ``device``; None where they do not, or where the
    trace has no window or no clock offset can be found."""
    workers = chip_workers(ctx)
    if not workers:
        return None
    pl = progtrace.planes(ctx)
    win = devtrace.window_ns(pl) if pl else None
    off = progtrace.offset_ns(ctx, pl) if win else None
    if off is None:
        return None
    return pl, win, off, workers


def intervals(ctx, name: str, tids, shift: float) -> list:
    """Sorted (start, end) on the profile's clock of the ``name`` spans
    recorded on the threads ``tids``."""
    return sorted((ev["ts"] * 1e3 + shift, (ev["ts"] + ev["dur"]) * 1e3
                   + shift)
                  for ev in progtrace.spans(ctx, name) if ev["tid"] in tids)


def starts_inside(starts, spans) -> int:
    """How many of the instants ``starts`` fall inside one of the sorted,
    disjoint (start, end) ``spans``."""
    firsts, ends = [a for a, _ in spans], [b for _, b in spans]
    return sum(progtrace._covering(firsts, ends, t) for t in starts)
