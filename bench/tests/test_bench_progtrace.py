"""Program spans laid over the device trace: the clock offset, the idle
time by worker state, and the router's and the collector's shares."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import devtrace, progtrace, spec  # noqa: E402

MS = 1e6                # ns
OFFSET = 5 * MS         # profile ns = ts * 1e3 + OFFSET
WORKER, CLIENT, OTHER = 7, 3, 9


def _span(name, a_ms, b_ms, tid=WORKER, **args):
    """A program span whose interval is [a_ms, b_ms] on the profile's
    clock."""
    ts = (a_ms * MS - OFFSET) / 1e3
    return {"ph": "X", "name": name, "tid": tid, "ts": ts,
            "dur": (b_ms - a_ms) * 1e3, "args": args}


def _spans():
    """Two batches on the worker; a full collection on another thread
    while the worker waits, one on the worker itself; two answered
    requests and a shed one.  Each line is [start, end] in ms."""
    out = []
    for seq, (c0, c1) in enumerate([(0, 8), (24, 38)]):
        b = c1
        out += [
            _span("collect", c0, c1, cell="c0", seq=seq),
            _span("batch", b, b + 1, cell="c0", seq=seq),
            _span("dispatch", b + 1, b + 13, cell="c0", seq=seq),
            _span("kernel", b + 1.5, b + 12.5),
            _span("backend.launch", b + 1.5, b + 2.5),
            _span("backend.wait", b + 2.5, b + 12.5),
            _span("rerank", b + 12.5, b + 13),
            _span("deliver", b + 13, b + 15, cell="c0", seq=seq),
        ]
    out += [
        _span("collect", 53, 100, cell="c0", seq=2),
        _span("gc", 30, 35, tid=OTHER, generation=2, collected=12),
        _span("gc", 60, 62, generation=2, collected=3),
        _span("jax-compile", 70, 71, stage="backend_compile_duration",
              fun="f"),
        _span("route", 6, 24, tid=CLIENT, trace_id=100, outcome="ok"),
        _span("queue", 7, 8, cell="c0", seq=0, trace_id=100),
        _span("route", 35, 55, tid=CLIENT, trace_id=101, outcome="ok"),
        _span("queue", 36.5, 38, cell="c0", seq=1, trace_id=101),
        _span("route", 70, 70.1, tid=CLIENT, trace_id=102,
              outcome="shed"),
    ]
    return out


def _planes(lead_ns=0.0, lag_ns=0.0):
    """A 100 ms window; the device busy over 10-20 and 40-50 ms, one
    program starting at 10 and one at 40; a ``client.search`` annotation
    opening ``lead_ns`` before each route span and closing ``lag_ns``
    after it."""
    client = [(progtrace.CLIENT, ev["ts"] * 1e3 + OFFSET - lead_ns,
               ev["dur"] * 1e3 + lead_ns + lag_ns)
              for ev in _spans() if ev["name"] == "route"]
    host = ("/host:CPU", [("python", [(devtrace.WINDOW, 0.0, 100 * MS)]
                           + client)])
    dev = ("/device:TPU:0", [
        ("XLA Modules", [("jit_search", 10 * MS, 10 * MS),
                         ("jit_search", 40 * MS, 10 * MS)]),
        ("XLA Ops", [("fusion.1", 10 * MS, 10 * MS),
                     ("fusion.2", 40 * MS, 10 * MS)]),
    ])
    return [host, ("Task Environment", []), dev]


def _ctx(exact=True, spans=None, planes=None):
    ctx = SimpleNamespace(spans=_spans() if spans is None else spans,
                          planes=_planes() if planes is None else planes)
    if exact:
        ctx.profile_start_ns = 10**18
        ctx.clock_anchor = {"perf_counter_ns": 1,
                            "time_ns": 10**18 + int(OFFSET)}
    return ctx


def _metric(name):
    return spec.reader(name)


def test_idle_time_splits_by_worker_state_and_sums_to_device_idle():
    ctx = _ctx()
    att = progtrace.attribute(ctx)
    split = att["split_s"]
    want = {"collect": 0.062, "batch": 0.002, "dispatch": 0.001,
            "backend.launch": 0.001, "backend.wait": 0.001,
            "rerank": 0.001, "deliver": 0.004, "gc": 0.007,
            "between spans": 0.001}
    assert split == pytest.approx(want)
    assert att["idle_s"] == pytest.approx(0.08)
    assert sum(split.values()) == pytest.approx(att["idle_s"])
    idle_host = _metric("idle_host.steady").read(ctx)
    assert idle_host == pytest.approx(0.18)
    ctx.device = {"window_s": 0.1, "busy_s": 0.02}
    device_idle = _metric("device_idle.steady").read(ctx)
    assert idle_host + split["collect"] / att["window_s"] == \
        pytest.approx(device_idle)


def test_gaps_are_named_by_the_state_that_held_most_of_each():
    att = progtrace.attribute(_ctx())
    assert [(round(s, 9), state) for s, state in att["gaps"]] == [
        (0.05, "collect"), (0.02, "collect"), (0.01, "collect")]
    note = _metric("idle_host.bulk").describe(_ctx())
    assert "between spans" in note and "2 of 2" in note


def test_programs_start_inside_dispatch_and_compiles_are_counted():
    att = progtrace.attribute(_ctx())
    assert att["modules"] == (2, 2)
    assert att["compiles"] == 1
    # a clock off by 2 ms puts both program starts outside dispatch
    ctx = _ctx()
    ctx.clock_anchor = dict(ctx.clock_anchor,
                            time_ns=ctx.clock_anchor["time_ns"] + 2 * MS)
    assert progtrace.attribute(ctx)["modules"] == (0, 2)


def test_gc_share_is_full_collections_over_the_window():
    assert _metric("gc_share.steady").read(_ctx()) == pytest.approx(0.07)
    assert "full collections in the window: 2" in \
        _metric("gc_share.steady").describe(_ctx())


def test_route_ms_is_the_route_time_outside_the_cell():
    # request 100: (7 - 6) + (24 - 21) = 4 ms; request 101: (36.5 - 35)
    # + (55 - 51) = 5.5 ms; the shed request is left out
    assert progtrace.route_outside_ms(_ctx()) == pytest.approx([4.0, 5.5])
    assert _metric("route_ms").read(_ctx()) == pytest.approx(4.75)


def test_route_ms_joins_each_option_group_to_its_own_dispatch():
    """One collection served as two dispatches: a request of the second
    group is timed from the end of the second dispatch."""
    spans = [
        _span("collect", 0, 1, cell="c0", seq=0),
        _span("dispatch", 2, 10, cell="c0", seq=0, group=0),
        _span("dispatch", 12, 20, cell="c0", seq=0, group=1),
        _span("route", 0.5, 21, tid=CLIENT, trace_id=200, outcome="ok"),
        _span("queue", 0.5, 1, cell="c0", seq=0, group=1, trace_id=200),
    ]
    # (0.5 - 0.5) + (21 - 20) = 1 ms, not 11 from group 0's end
    assert progtrace.route_outside_ms(_ctx(spans=spans)) == \
        pytest.approx([1.0])


def test_paired_offset_matches_the_anchor():
    ctx = _ctx(exact=False, planes=_planes(lead_ns=8e3, lag_ns=12e3))
    off, how, err = progtrace.offset_ns(ctx, ctx.planes)
    assert how == "paired"
    assert off == pytest.approx(OFFSET + 2e3)
    assert err == pytest.approx(10e3)
    exact = progtrace.offset_ns(_ctx(), _planes())
    assert exact == (OFFSET, "anchor", 0.0)
    ctx = _ctx(exact=False, planes=_planes(lead_ns=10e3, lag_ns=10e3))
    assert _metric("idle_host.steady").read(ctx) == pytest.approx(0.18)


def test_a_program_without_the_spans_reads_nothing():
    """The parent program records no collect, seq or gc: every reader
    returns None and none raises."""
    old = [dict(ev, args={k: v for k, v in ev["args"].items()
                          if k != "seq"})
           for ev in _spans()
           if ev["name"] not in ("collect", "deliver", "gc",
                                 "backend.launch", "backend.wait")]
    for name in ("idle_host.steady", "idle_host.bulk", "gc_share.steady",
                 "route_ms"):
        m = _metric(name)
        assert m.read(_ctx(spans=old)) is None
        assert m.describe(_ctx(spans=old)) is None
    assert _metric("idle_host.steady").read(_ctx(spans=[])) is None
    no_device = [_planes()[0]]
    assert _metric("gc_share.steady").read(_ctx(planes=no_device)) is None


def test_planes_are_found_in_the_harness_run_frame():
    g = {"__name__": "bench.harness", "progtrace": progtrace}
    exec("def run(ctx, planes):\n"
         "    rec = {'planes': planes}\n"
         "    return progtrace.planes(ctx)\n", g)
    ctx = SimpleNamespace(spans=[])
    assert g["run"](ctx, ["p"]) == ["p"]
    assert progtrace.planes(SimpleNamespace(spans=None)) is None
    assert progtrace.planes(SimpleNamespace(planes=["q"])) == ["q"]


def test_a_traced_run_without_planes_fails_loudly():
    """Spans but no planes, in ctx or in the harness frame: the lookup
    the readers depend on has broken, and the run must say so."""
    with pytest.raises(LookupError):
        progtrace.planes(SimpleNamespace(spans=[]))
    spans = _spans()
    with pytest.raises(LookupError):
        _metric("idle_host.steady").read(SimpleNamespace(spans=spans))
