"""Each chip paired with its own worker: the idle time the host holds a
chip back, and how long a finished result waits for its worker."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import chiptrace, devtrace, spec  # noqa: E402

MS = 1e6                # ns
OFFSET = 5 * MS         # profile ns = ts * 1e3 + OFFSET
A, B, OTHER = 7, 8, 9   # worker of chip 0, worker of chip 1, another thread


def _span(name, a_ms, b_ms, tid, **args):
    ts = (a_ms * MS - OFFSET) / 1e3
    return {"ph": "X", "name": name, "tid": tid, "ts": ts,
            "dur": (b_ms - a_ms) * 1e3, "args": args}


def _spans():
    """Worker A drives chip 0 and waits in ``collect`` over 20-40 ms while
    worker B, which drives chip 1, assembles a batch; a full collection
    on another thread over 25-27 ms.  Each line is [start, end] in ms."""
    return [
        _span("collect", 0, 10, A, cell="c0", seq=0, device=0),
        _span("dispatch", 10, 20, A, cell="c0", seq=0, device=0),
        _span("backend.wait", 11, 19.8, A),
        _span("collect", 20, 40, A, cell="c0", seq=1, device=0),
        _span("dispatch", 40, 50, A, cell="c0", seq=1, device=0),
        _span("backend.wait", 41, 49.6, A),
        _span("deliver", 50, 52, A, cell="c0", seq=1),
        _span("collect", 52, 100, A, cell="c0", seq=2, device=0),
        _span("collect", 0, 20, B, cell="c1", seq=0, device=1),
        _span("batch", 20, 40, B, cell="c1", seq=0),
        _span("dispatch", 40, 60, B, cell="c1", seq=0, device=1),
        _span("backend.wait", 41, 57, B),
        _span("collect", 60, 100, B, cell="c1", seq=1, device=1),
        _span("gc", 25, 27, OTHER, generation=2, collected=5),
    ]


def _device(n, programs):
    evs = [("jit_search", a * MS, (b - a) * MS) for a, b in programs]
    return (f"/device:TPU:{n}", [("XLA Modules", evs), ("XLA Ops", evs)])


def _planes(chips=(0, 1)):
    """A 100 ms window; chip 0 runs programs over 10.5-19.5 and 40.5-49
    ms, chip 1 over 2-8 (outside any dispatch) and 45-55 ms."""
    host = ("/host:CPU", [("python", [(devtrace.WINDOW, 0.0, 100 * MS)])])
    devs = {0: _device(0, [(10.5, 19.5), (40.5, 49.0)]),
            1: _device(1, [(2, 8), (45, 55)])}
    return [host] + [devs[c] for c in chips]


def _ctx(spans=None, planes=None):
    return SimpleNamespace(
        spans=_spans() if spans is None else spans,
        planes=_planes() if planes is None else planes,
        profile_start_ns=10**18,
        clock_anchor={"perf_counter_ns": 1,
                      "time_ns": 10**18 + int(OFFSET)})


def _metric(name):
    return spec.reader(name)


def test_each_chip_idle_is_held_only_by_its_own_worker():
    """Chip 0 idles over 20-40 ms while its worker waits in collect:
    worker B's batch then does not count there, the collection does.
    Chip 0: 4.5 ms of its own worker outside collect + 2 ms of gc;
    chip 1: batch 20 + wait 5 + 2 + dispatch 3 ms."""
    m = _metric("idle_host_per_chip.x4")
    ctx = _ctx()
    per = m.per_chip(ctx)
    assert sorted(per) == [0, 1]
    split0 = per[0]["split_s"]
    assert split0["batch"] == 0.0
    assert split0["gc"] == pytest.approx(0.002)
    # 10 + 20 + 48 ms in collect, less the 2 ms the collection held
    assert split0["collect"] == pytest.approx(0.076)
    assert per[1]["split_s"]["batch"] == pytest.approx(0.018)
    assert per[1]["split_s"]["gc"] == pytest.approx(0.002)
    assert m.read(ctx) == pytest.approx((0.065 + 0.30) / 2)
    note = m.describe(_ctx())
    assert "chip 0: " in note and "chip 1: " in note
    # chip 0 starts both programs inside worker A's dispatch spans;
    # chip 1 one of two (2-8 ms falls in worker B's collect)
    assert "dispatch: 2 of 2" in note and "dispatch: 1 of 2" in note


def test_one_chip_one_worker_reads_as_idle_host():
    spans = [ev for ev in _spans() if ev["tid"] != B]
    ctx = _ctx(spans=spans, planes=_planes(chips=(0,)))
    per_chip = _metric("idle_host_per_chip.x4").read(ctx)
    assert per_chip == pytest.approx(0.065)
    assert _metric("idle_host.steady").read(
        _ctx(spans=spans, planes=_planes(chips=(0,)))) == \
        pytest.approx(per_chip)


def test_wake_lag_is_wait_end_minus_the_chip_done():
    """Chip 0: 19.8 - 19.5 and 49.6 - 49.0 ms; chip 1: 57 - 55 ms; its
    program at 2-8 ms started outside every dispatch and is left out."""
    m = _metric("wake_lag_ms.x4")
    ctx = _ctx()
    assert m.lags(ctx) == {(0,): pytest.approx([0.3, 0.6]),
                           (1,): pytest.approx([2.0])}
    assert m.read(ctx) == pytest.approx(0.6)
    note = m.describe(_ctx())
    assert "chip 0: 2 dispatches" in note and "chip 1: 1 dispatches" in note


def test_a_cell_of_several_chips_waits_on_the_last_of_them():
    spans = [_span("dispatch", 40, 60, B, cell="c1", seq=0, device=[0, 1]),
             _span("backend.wait", 41, 57, B),
             _span("collect", 0, 40, B, cell="c1", seq=0, device=[0, 1])]
    ctx = _ctx(spans=spans)
    assert chiptrace.chip_workers(ctx) == {0: {B}, 1: {B}}
    # chip 0's program ends at 49, chip 1's at 55: the wait ends at 57
    assert _metric("wake_lag_ms.x4").lags(ctx) == \
        {(0, 1): pytest.approx([2.0])}


def test_spans_without_device_read_nothing():
    """The parent program tags no span with its chip: both readers read
    None and neither raises, planes or not."""
    bare = [dict(ev, args={k: v for k, v in ev["args"].items()
                           if k != "device"}) for ev in _spans()]
    for name in ("idle_host_per_chip.x4", "wake_lag_ms.x4"):
        m = _metric(name)
        assert m.read(_ctx(spans=bare)) is None
        assert m.describe(_ctx(spans=bare)) is None
        assert m.read(SimpleNamespace(spans=bare)) is None
        assert m.read(SimpleNamespace(spans=None)) is None
