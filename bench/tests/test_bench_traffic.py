"""Arrival schedules, due-time latency and the percentile of all requests."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import sys
import threading
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src")]

from bench import traffic as tr  # noqa: E402


def test_poisson_gaps_fill_the_window_with_the_same_work_every_seed():
    a = tr.poisson_gaps(2000.0, 10.0, seed=1)
    b = tr.poisson_gaps(2000.0, 10.0, seed=2**31 + 5)
    assert a.size == b.size == 20000
    assert a.sum() == pytest.approx(10.0)
    assert np.array_equal(np.sort(a), np.sort(b))      # same gaps ...
    assert not np.array_equal(a, b)                    # ... other order
    # exponential law: mean 1/rate, sd 1/rate, median ln2/rate
    assert a.mean() == pytest.approx(1 / 2000, rel=1e-9)
    assert a.std() == pytest.approx(1 / 2000, rel=0.05)
    assert np.median(a) == pytest.approx(np.log(2) / 2000, rel=0.02)


def test_due_times_start_at_zero_and_rise():
    due = tr.due_times(100.0, 2.0, seed=3)
    assert due[0] == 0.0 and due.size == 200
    assert (np.diff(due) > 0).all() and due[-1] < 2.0
    assert np.array_equal(due, tr.due_times(100.0, 2.0, seed=3))


def test_percentile_is_the_nearest_rank_of_every_request():
    v = np.arange(1, 101, dtype=float)          # 1..100
    assert tr.percentile(v, 50) == 50.0
    assert tr.percentile(v, 99) == 99.0
    assert tr.percentile(v, 100) == 100.0
    # one failed request in a hundred is the 100th value, not the 99th
    v[3] = np.inf
    assert tr.percentile(v, 99) == 100.0
    v[4] = np.inf
    assert tr.percentile(v, 99) == np.inf
    assert tr.percentile(np.array([7.0]), 99) == 7.0


def test_latency_runs_from_due_time_and_misses_on_failure():
    w = tr.Window(t0=10.0, seconds=1.0, query=np.zeros(3, int),
                  due=np.array([10.0, 10.1, 10.2]),
                  sent=np.array([10.0, 10.15, 10.2]),
                  done=np.array([10.004, 10.16, 10.3]),
                  status=np.array([tr.OK, tr.OK, tr.SHED]),
                  dists=np.zeros((3, 1)), ids=np.zeros((3, 1)), errors=[])
    lat = w.latency_ms()
    assert lat[0] == pytest.approx(4.0)
    assert lat[1] == pytest.approx(60.0)      # 50 ms late + 10 ms served
    assert lat[2] == np.inf
    assert w.answered_in_window() == 2


def _fake_search(stall_at=None, stall_s=0.0, service_s=0.001):
    """Answers after ``service_s``; the ``stall_at``-th call holds a lock
    for ``stall_s`` that every other call waits on (a stalled server)."""
    lock = threading.Lock()
    calls = iter(range(10**9))

    def search(q, timeout):
        with lock:
            if next(calls) == stall_at:
                time.sleep(stall_s)
        time.sleep(service_s)
        return np.zeros(2, np.float32), np.arange(2)
    return search


def test_open_loop_counts_a_stall_against_the_requests_behind_it():
    pool = np.zeros((16, 4), np.float32)
    w = tr.open_loop(_fake_search(stall_at=20, stall_s=0.2), pool,
                     rate=200.0, seconds=1.0, clients=32, seed=5, k=2)
    assert w.n == 200 and (w.status == tr.OK).all()
    lat = w.latency_ms()
    # requests due during the 200 ms stall wait for it: tens of them
    # are 50 ms late or more, though each is served in about 1 ms
    assert (lat > 50).sum() >= 20
    assert tr.percentile(lat, 50) < 50


def test_closed_loop_sends_the_next_request_after_the_answer():
    pool = np.zeros((16, 4), np.float32)
    w = tr.closed_loop(_fake_search(service_s=0.01), pool, seconds=0.5,
                       clients=4, seed=5, k=2)
    # 4 callers x ~10 ms a request: ~200 requests in 0.5 s
    assert 100 <= w.answered_in_window() <= 220
    assert (w.latency_ms()[w.status == tr.OK] >= 10).all()


def test_shed_requests_are_refused_not_lost():
    from repro.serve.fleet import FleetOverloadError

    def search(q, timeout):
        raise FleetOverloadError("full")

    w = tr.open_loop(search, np.zeros((4, 2), np.float32), rate=50.0,
                     seconds=0.2, clients=2, seed=1, k=2)
    assert (w.status == tr.SHED).all()


def test_latency_readers_take_every_request_of_the_window():
    from bench import spec

    lat_w = SimpleNamespace(latency_ms=lambda: np.r_[np.ones(98), 5, 9.0])
    ctx = SimpleNamespace(traffic={"loop": "open"}, window=lat_w)
    assert spec.reader("p50_ms").read(ctx) == 1.0
    assert spec.reader("p99_ms").read(ctx) == 5.0
    closed = SimpleNamespace(traffic={"loop": "closed"}, window=SimpleNamespace(
        answered_in_window=lambda: 500, seconds=10.0))
    assert spec.reader("qps").read(closed) == 50.0
    assert spec.reader("p99_ms").read(closed) is None
