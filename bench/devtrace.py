"""Device trace: record a window with the JAX profiler and reduce it.

The trace is read into plain tuples — ``planes = [(plane, [(line,
[(name, start_ns, dur_ns), ...]), ...]), ...]`` — so the reduction below
is pure arithmetic that tests can feed by hand.  Device planes are
``/device:<PLATFORM>:<n>``; their ``XLA Ops`` line holds one event per
executed operation and ``XLA Modules`` one per executed program.  The
window is the benchmark's own ``bench.window`` host annotation.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from contextlib import contextmanager

WINDOW = "bench.window"
_DEVICE = re.compile(r"^/device:[A-Z]+:\d+$")
_SUFFIX = re.compile(r"(\.\d+)+$")
_HLO = re.compile(r"^%?([\w\-]+?)(?:\.\d+)*\s*=\s*(\S+)")
_LAYOUT = re.compile(r"\{[^}]*\}")


@contextmanager
def recording(enabled: bool):
    """Profile the enclosed block when ``enabled``; yields a dict that
    holds the trace's planes (see module doc) after the block."""
    out: dict = {}
    if not enabled:
        yield out
        return
    import jax

    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW):
                yield out
        finally:
            jax.profiler.stop_trace()
        out["planes"] = load(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def load(directory: str) -> list:
    """Planes of the newest ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return []
    pd = ProfileData.from_file(files[-1])
    return [(pl.name, [(ln.name, [(ev.name, float(ev.start_ns),
                                   float(ev.duration_ns))
                                  for ev in ln.events])
                       for ln in pl.lines])
            for pl in pd.planes]


def window_ns(planes) -> tuple[float, float] | None:
    """(start, end) of the ``bench.window`` annotation."""
    for name, lines in planes:
        if _DEVICE.match(name):
            continue
        for _, events in lines:
            for ev, s, d in events:
                if ev == WINDOW:
                    return s, s + d
    return None


def device_lines(planes, line: str) -> list:
    """Per device plane, its events on ``line`` (falling back to
    ``XLA Modules`` where the plane has no such line)."""
    out = []
    for name, lines in planes:
        if not _DEVICE.match(name):
            continue
        by = dict(lines)
        evs = by.get(line) or by.get("XLA Modules") or []
        out.append(evs)
    return out


def clip(events, lo: float, hi: float) -> list:
    """Intervals of ``events`` cut to [lo, hi], empty ones dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b))
    return out


def union(intervals) -> list:
    """Merged, sorted [(a, b)] covering every (name, a, b) interval."""
    merged: list = []
    for _, a, b in sorted(intervals, key=lambda t: t[1]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def op_group(name: str) -> str:
    """An op's name without XLA's numeric suffixes: ``fusion.12`` and
    ``fusion.3`` are both ``fusion``.  A TPU trace names an op by its HLO
    text (``%copy.25 = f32[8192,306,128]{2,1,0} copy(...)``): that groups
    by the op and its result type without layout (``copy
    f32[8192,306,128]``), or the op alone where the result is a tuple."""
    m = _HLO.match(name)
    if m is None:
        return _SUFFIX.sub("", name) or name
    op, result = m.group(1), _LAYOUT.sub("", m.group(2)).rstrip(",")
    return op if result.startswith("(") else f"{op} {result}"


def reduce(planes, n_gaps: int = 10) -> dict | None:
    """Busy and window seconds, device-op and module time, idle gaps.

    Returns None where the trace holds no window or no device plane.
    ``busy_s`` is the union of the ops' intervals inside the window,
    averaged over the device planes; ``ops`` sums op time by group,
    averaged likewise; ``modules_s`` sums program time over every
    device; ``gaps`` lists the ``n_gaps``
    longest idle intervals of the first device, each with what the host
    was doing in its middle."""
    win = window_ns(planes)
    devs = device_lines(planes, "XLA Ops")
    if win is None or not devs:
        return None
    lo, hi = win
    busy, ops = [], {}
    for evs in devs:
        cut = clip(evs, lo, hi)
        busy.append(sum(b - a for a, b in union(cut)))
        for name, a, b in cut:
            g = op_group(name)
            ops[g] = ops.get(g, 0.0) + (b - a)
    modules = [clip(evs, lo, hi) for evs in device_lines(planes,
                                                         "XLA Modules")]
    module_ns = sum(b - a for m in modules for _, a, b in m)
    spans = union(clip(devs[0], lo, hi))
    gaps, t = [], lo
    for a, b in spans + [(hi, hi)]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:n_gaps]
    host = host_events(planes, lo, hi)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "modules_s": module_ns / 1e9,
        "ops": sorted(((g, ns / 1e9 / len(devs)) for g, ns in ops.items()),
                      key=lambda t: -t[1]),
        "gaps": [(host_activity(host, (a + b) / 2), (b - a) / 1e9)
                 for a, b in longest],
    }


def host_events(planes, lo: float, hi: float) -> list:
    """Host events that overlap [lo, hi], shortest first, the window's
    own annotation left out."""
    out = []
    for name, lines in planes:
        if _DEVICE.match(name):
            continue
        for _, events in lines:
            out.extend((ev, s, s + d) for ev, s, d in events
                       if ev != WINDOW and d > 0 and s < hi and s + d > lo)
    return sorted(out, key=lambda t: t[2] - t[1])


def host_activity(host: list, t: float) -> str:
    """The innermost (shortest) host event open at ``t``, or ``no host
    event`` where none is."""
    for name, a, b in host:
        if a <= t < b:
            return name
    return "no host event"
