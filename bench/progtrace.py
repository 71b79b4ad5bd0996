"""The program's own spans laid over the device trace.

A traced run holds two records of the window: the profiler trace
(``bench.devtrace``: device ops and host annotations, ns from the
profile's start) and the program tracer's raw spans (``ctx.spans``:
Chrome events, ``ts``/``dur`` in us from the tracer's clock anchor).  One
offset puts a span on the profile's clock: ``ts * 1e3 + offset`` ns.  It
is known exactly where ``ctx`` carries the profile's start
(``profile_start_ns``, the Task Environment plane's
``profile_start_time``) and the tracer's ``clock_anchor``; otherwise it is
measured from the requests themselves: each one's ``client.search``
annotation (profile clock) opens just before its ``route`` span (program
clock) and closes just after it, so the mean of the median start and the
median end differences, rank against rank, is the offset to within a few
microseconds.

On that clock every instant of the window has a state of the cell's
batch worker, the innermost of its spans open then: ``collect`` (blocked
waiting for a batch's first request), ``batch``, ``backend.launch``,
``backend.wait``, ``rerank``, ``deliver``, ``dispatch`` (inside the
dispatch span but outside those), or ``between spans``; or ``gc`` while a
full collection holds the interpreter lock, whatever the worker was in.
``attribute`` splits the device's idle time by that state.
"""
from __future__ import annotations

import bisect
import sys
from collections import defaultdict

import numpy as np

from bench import devtrace

CLIENT = "client.search"
WORKER = {"collect": "collect", "batch": "batch", "dispatch": "dispatch",
          "kernel": "dispatch", "backend.launch": "backend.launch",
          "backend.wait": "backend.wait", "rerank": "rerank",
          "deliver": "deliver"}
STATES = ("collect", "batch", "backend.launch", "backend.wait", "rerank",
          "deliver", "dispatch", "gc", "between spans")


def planes(ctx):
    """The traced window's planes: ``ctx.planes``, or, where the harness
    does not pass them, its ``run``'s own ``rec`` (the dict that
    ``devtrace.recording`` filled), found up the call stack.  Raises
    where a traced run (``ctx.spans`` set) has neither, so a renamed
    local in the harness fails the run instead of silencing every
    metric read from here."""
    found = getattr(ctx, "planes", None)
    frame = sys._getframe(1)
    while found is None and frame is not None:
        rec = frame.f_locals.get("rec")
        if (frame.f_code.co_name == "run"
                and frame.f_globals.get("__name__") == "bench.harness"
                and isinstance(rec, dict)):
            found = rec.get("planes")
        frame = frame.f_back
    if found is None and getattr(ctx, "spans", None) is not None:
        raise LookupError("a traced run's planes are neither in ctx nor "
                          "in bench.harness.run's 'rec'")
    return found


def spans(ctx, name: str | None = None) -> list:
    """Complete (``ph`` X) program spans, of ``name`` where given."""
    return [ev for ev in ctx.spans or ()
            if ev.get("ph") == "X" and (name is None or ev["name"] == name)]


def _host_starts_ends(planes_, name: str):
    s, e = [], []
    for plane, lines in planes_:
        if devtrace._DEVICE.match(plane):
            continue
        for _, events in lines:
            for ev, start, dur in events:
                if ev == name:
                    s.append(start)
                    e.append(start + dur)
    return np.sort(s), np.sort(e)


def offset_ns(ctx, planes_):
    """(ns to add to ``ts * 1e3``, how it was found, its uncertainty in
    ns); None where neither way applies."""
    start = getattr(ctx, "profile_start_ns", None)
    anchor = getattr(ctx, "clock_anchor", None)
    if start is not None and anchor is not None:
        return float(anchor["time_ns"] - start), "anchor", 0.0
    a_s, a_e = _host_starts_ends(planes_, CLIENT)
    routes = spans(ctx, "route")
    r_s = np.sort([ev["ts"] * 1e3 for ev in routes])
    r_e = np.sort([(ev["ts"] + ev["dur"]) * 1e3 for ev in routes])
    if a_s.size == 0 or a_s.size != r_s.size:
        return None
    lead = float(np.median(a_s - r_s))     # offset - (annotation lead)
    lag = float(np.median(a_e - r_e))      # offset + (annotation lag)
    return (lead + lag) / 2, "paired", (lag - lead) / 2


def _covering(starts, ends, t) -> bool:
    j = bisect.bisect_right(starts, t) - 1
    return j >= 0 and t < ends[j]


def attribute(ctx):
    """The window's idle time by worker state, and the checks on the
    clocks; None where the trace has no window or device, the program
    records no ``collect`` span, or no offset can be found.  Cached on
    ``ctx``."""
    if hasattr(ctx, "progtrace"):
        return ctx.progtrace
    ctx.progtrace = out = None
    pl = planes(ctx) if ctx.spans else None
    win = devtrace.window_ns(pl) if pl else None
    devs = devtrace.device_lines(pl, "XLA Ops") if win else []
    workers = {ev["tid"] for ev in spans(ctx, "collect")}
    off = offset_ns(ctx, pl) if devs and workers else None
    if off is not None:
        ctx.progtrace = out = _attribute(ctx, pl, win, devs[0], workers,
                                         off)
    return out


def _attribute(ctx, pl, win, ops, workers, off):
    lo, hi = win
    shift = off[0]
    conv = [(ev, ev["ts"] * 1e3 + shift, (ev["ts"] + ev["dur"]) * 1e3
             + shift) for ev in spans(ctx)]
    gaps, t = [], lo
    for a, b in devtrace.union(devtrace.clip(ops, lo, hi)) + [(hi, hi)]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    # a sweep over every span, collection and gap boundary: closes sort
    # before opens, so back-to-back spans never overlap
    marks = []
    for k, (ev, a, b) in enumerate(conv):
        if b <= a:
            continue
        if ev["name"] == "gc":
            marks += [(a, 1, "gc", k), (b, 0, "gc", k)]
        elif ev["tid"] in workers and ev["name"] in WORKER:
            marks += [(a, 1, "w", k), (b, 0, "w", k)]
    for k, (a, b) in enumerate(gaps):
        marks += [(a, 1, "idle", k), (b, 0, "idle", k)]
    marks.sort(key=lambda m: (m[0], m[1]))
    split = defaultdict(float)
    per_gap = defaultdict(lambda: defaultdict(float))
    active, n_gc, gap, t = {}, 0, None, lo
    for when, opens, kind, k in marks:
        a, b = max(t, lo), min(when, hi)
        if gap is not None and b > a:
            state = ("gc" if n_gc else min(active.values())[1] if active
                     else "between spans")
            split[state] += b - a
            per_gap[gap][state] += b - a
        t = when
        if kind == "idle":
            gap = k if opens else None
        elif kind == "gc":
            n_gc += 1 if opens else -1
        elif opens:
            ev, s, e = conv[k]
            active[k] = (e - s, WORKER[ev["name"]])
        else:
            active.pop(k, None)
    longest = sorted(range(len(gaps)), key=lambda g: gaps[g][0] - gaps[g][1])
    named = []
    for g in longest[:10]:
        parts = per_gap.get(g) or {"between spans": 0.0}
        named.append(((gaps[g][1] - gaps[g][0]) / 1e9,
                      max(parts, key=parts.get)))
    disp = sorted((a, b) for ev, a, b in conv
                  if ev["name"] == "dispatch" and ev["tid"] in workers)
    d_s, d_e = [a for a, _ in disp], [b for _, b in disp]
    modules = [s for _, s, _ in devtrace.device_lines(pl, "XLA Modules")[0]
               if lo <= s < hi]
    inside = sum(_covering(d_s, d_e, s) for s in modules)
    gc_in = devtrace.union([("gc", max(a, lo), min(b, hi))
                            for ev, a, b in conv
                            if ev["name"] == "gc" and b > lo and a < hi])
    compiles = sum(1 for ev, a, b in conv
                   if ev["name"] == "jax-compile" and b > lo and a < hi)
    return {
        "window_s": (hi - lo) / 1e9,
        "idle_s": sum(b - a for a, b in gaps) / 1e9,
        "split_s": {s: split.get(s, 0.0) / 1e9 for s in STATES},
        "gaps": named,
        "modules": (inside, len(modules)),
        "gc_s": sum(b - a for a, b in gc_in) / 1e9,
        "gc_n": sum(1 for ev, a, b in conv
                    if ev["name"] == "gc" and b > lo and a < hi),
        "compiles": compiles,
        "offset": off,
    }


def route_outside_ms(ctx) -> list:
    """Per answered request, the time its ``route`` span spent outside
    the cell (ms): from route start to its ``queue`` span's start, plus
    from the end of the ``dispatch`` that served it (joined by the cell,
    ``seq`` and ``group`` the queue span carries) to route end.  Empty
    where the spans carry no ``seq``."""
    def key(ev):
        a = ev["args"]
        return a.get("cell"), a.get("seq"), a.get("group", 0)

    ends = {key(ev): ev["ts"] + ev["dur"]
            for ev in spans(ctx, "dispatch") if "seq" in ev["args"]}
    queued = defaultdict(list)
    for ev in spans(ctx, "queue"):
        if key(ev) in ends:
            queued[ev["args"]["trace_id"]].append((ends[key(ev)], ev["ts"]))
    out = []
    for ev in spans(ctx, "route"):
        got = queued.get(ev["args"]["trace_id"])
        if ev["args"].get("outcome") != "ok" or not got:
            continue
        d_end, q_start = min(got)
        out.append(((q_start - ev["ts"]) + (ev["ts"] + ev["dur"] - d_end))
                   / 1e3)
    return out
