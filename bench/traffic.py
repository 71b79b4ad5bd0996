"""Load generation: arrival schedules, client threads, latency arithmetic.

Two loop kinds, named in a traffic file's ``loop``:

* ``open`` — independent users.  Requests are due on a Poisson schedule
  at ``rate_per_s`` whether or not earlier ones have finished; ``clients``
  threads take the requests in due order, each sleeping until its
  request is due.  Latency runs from the due time to the answer, so a
  stalled server or a starved generator delays the requests behind it.
* ``closed`` — ``clients`` callers that each wait for their answer
  before sending the next request.

Every request goes to ``search(query) -> (dists, ids)``; the pool of
queries is cycled in a seeded order.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import threading
import time
from contextlib import nullcontext

import numpy as np

OK, SHED, LOST = 0, 1, 2


def poisson_gaps(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Inter-arrival gaps of a Poisson process at ``rate`` over ``seconds``.

    Every seed gets the same multiset of gaps — the exponential law's
    quantiles at ``round(rate * seconds)`` even steps, scaled to fill the
    window exactly — in its own order, so seeds change the order of
    arrivals and never the amount of work."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    return np.random.default_rng(np.random.SeedSequence([int(seed), 3])) \
        .permutation(gaps)


def due_times(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due offsets (seconds from the window's start), the first at 0."""
    gaps = poisson_gaps(rate, seconds, seed)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def pool_order(n_requests: int, pool: int, seed: int) -> np.ndarray:
    """Which pool query each request sends: the pool in a seeded order,
    cycled."""
    perm = np.random.default_rng(np.random.SeedSequence([int(seed), 4])) \
        .permutation(pool)
    return perm[np.arange(n_requests) % pool]


def percentile(values: np.ndarray, q: float) -> float:
    """Nearest-rank ``q``-th percentile of every value (``inf`` for a
    request that failed): the smallest value with at least q% of all
    values at or below it."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return math.nan
    return float(v[max(0, math.ceil(q / 100.0 * v.size) - 1)])


@dataclasses.dataclass
class Window:
    """What the clients recorded; times are ``perf_counter`` seconds."""
    t0: float                 # window start
    seconds: float
    query: np.ndarray         # (N,) pool index sent
    due: np.ndarray           # (N,) absolute due time (open loop) or send
    sent: np.ndarray          # (N,)
    done: np.ndarray          # (N,)
    status: np.ndarray        # (N,) OK / SHED / LOST
    dists: np.ndarray         # (N, k)
    ids: np.ndarray           # (N, k)
    errors: list              # a few repr()s of LOST requests

    @property
    def n(self) -> int:
        return int(self.status.size)

    def latency_ms(self) -> np.ndarray:
        """Due-to-answer latency of every request, ``inf`` where it
        failed or was shed."""
        lat = (self.done - self.due) * 1e3
        return np.where(self.status == OK, lat, np.inf)

    def answered_in_window(self) -> int:
        return int(((self.status == OK)
                    & (self.done <= self.t0 + self.seconds)).sum())


def _serve_one(search, query, timeout, annotate):
    """(status, dists, ids, error) of one routed request."""
    from repro.serve.fleet import FleetOverloadError

    try:
        with annotate("client.search"):
            d, i = search(query, timeout)
        return OK, d, i, None
    except FleetOverloadError:
        return SHED, None, None, None
    except Exception as e:        # an answer that never came
        return LOST, None, None, e


def _empty(n: int, k: int):
    return (np.zeros(n, np.int64), np.zeros(n), np.zeros(n), np.zeros(n),
            np.full(n, LOST, np.int8), np.full((n, k), np.inf, np.float32),
            np.full((n, k), -1, np.int64))


def open_loop(search, pool: np.ndarray, *, rate: float, seconds: float,
              clients: int, seed: int, k: int, timeout: float = 60.0,
              trace: bool = False) -> Window:
    """Send requests on a Poisson schedule; returns once every request
    has an answer, a refusal, or an error."""
    import jax

    due = due_times(rate, seconds, seed)
    n = due.size
    qidx = pool_order(n, pool.shape[0], seed)
    _, _, sent, done, status, D, I = _empty(n, k)
    errors: list = []
    annotate = jax.profiler.TraceAnnotation if trace else _null
    counter = itertools.count()
    t0 = time.perf_counter() + 0.05
    due_abs = t0 + due

    def client():
        while True:
            j = next(counter)
            if j >= n:
                return
            wait = due_abs[j] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            sent[j] = time.perf_counter()
            st, d, i, err = _serve_one(search, pool[qidx[j]], timeout,
                                       annotate)
            done[j] = time.perf_counter()
            if st == OK:
                D[j], I[j] = d, i
            elif err is not None and len(errors) < 5:
                errors.append(repr(err))
            status[j] = st

    _run_threads(client, clients)
    return Window(t0, seconds, qidx, due_abs, sent, done, status, D, I,
                  errors)


def closed_loop(search, pool: np.ndarray, *, seconds: float, clients: int,
                seed: int, k: int, timeout: float = 60.0,
                trace: bool = False) -> Window:
    """``clients`` callers, each sending its next request when its last
    is answered, until ``seconds`` have passed."""
    import jax

    order = pool_order(pool.shape[0], pool.shape[0], seed)
    annotate = jax.profiler.TraceAnnotation if trace else _null
    counter = itertools.count()
    rows: list = [[] for _ in range(clients)]
    errors: list = []
    t0 = time.perf_counter() + 0.05
    t_end = t0 + seconds

    def client(c):
        wait = t0 - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        while True:
            t = time.perf_counter()
            if t >= t_end:
                return
            j = next(counter)
            q = order[j % order.size]
            st, d, i, err = _serve_one(search, pool[q], timeout, annotate)
            rows[c].append((j, q, t, time.perf_counter(), st, d, i))
            if err is not None and len(errors) < 5:
                errors.append(repr(err))

    _run_threads(client, clients, pass_index=True)
    flat = sorted((r for rs in rows for r in rs), key=lambda r: r[0])
    n = len(flat)
    qidx, _, sent, done, status, D, I = _empty(n, k)
    for j, (_, q, ts, td, st, d, i) in enumerate(flat):
        qidx[j], sent[j], done[j], status[j] = q, ts, td, st
        if st == OK:
            D[j], I[j] = d, i
    return Window(t0, seconds, qidx, sent.copy(), sent, done, status, D, I,
                  errors)


def _null(_name):
    return nullcontext()


def _run_threads(target, n: int, pass_index: bool = False) -> None:
    threads = [threading.Thread(target=target, args=(c,) if pass_index
                                else (), daemon=True) for c in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
