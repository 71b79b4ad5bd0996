"""Fleet tier tests: cell cancellation/failure semantics, router
admission/affinity/hedging/rerouting, leader fan-out, and submesh
partitioning.  Conformance (bitwise router-vs-engine and fan-out
parity) lives in test_conformance.py; these tests cover the *behavior*
the fleet adds on top of a correct cell."""
import queue
import threading
import time

import numpy as np
import pytest

from repro.obs.trace import Tracer, set_tracer
from repro.serve.cell import CellFailure, ServingCell
from repro.serve.fleet import CellRouter, FleetOverloadError, build_fleet


def _ok_fn(qs):
    b = qs.shape[0]
    return (np.zeros((b, 3), np.float32),
            np.tile(np.arange(3), (b, 1)).astype(np.int64))


def _slow_fn(delay_s):
    def fn(qs):
        time.sleep(delay_s)
        return _ok_fn(qs)

    return fn


def _query(rng):
    return rng.normal(size=(4,)).astype(np.float32)


def _query_for(router, rng, cell_name):
    """A query whose affinity-preferred cell is ``cell_name``."""
    for _ in range(1000):
        q = _query(rng)
        if router.preferred_cell(q).name == cell_name:
            return q
    raise AssertionError(f"no query routed to {cell_name} in 1000 draws")


# ---------------------------------------------------------------------------
# cell: timeout cancellation (the PR-7 leak fix) and failure sentinels
# ---------------------------------------------------------------------------


def test_cell_timeout_cancels_and_excludes_from_stats():
    """A timed-out request must be dropped by the batch worker — not
    computed anyway — and must never land in the latency stats (the
    pre-PR-7 leak: it stayed queued, was later served to nobody, and
    its enormous latency polluted the percentiles)."""
    cell = ServingCell(_slow_fn(0.3), name="slow", max_wait_ms=0.5)
    try:
        with pytest.raises(TimeoutError):
            cell.search(np.ones(4, np.float32), timeout=0.05)
        time.sleep(0.8)                      # let the worker churn past it
        st = cell.stats()
        assert st.cancelled == 1
        assert st.n == 0, "abandoned request landed in latency stats"
        # the cell still serves fine afterwards
        d, i = cell.search(np.ones(4, np.float32), timeout=5.0)
        assert d.shape == (3,)
        st = cell.stats()
        assert st.n == 1 and st.cancelled == 1
    finally:
        cell.close()


def test_cell_backend_failure_fails_fast_not_timeout():
    """A backend exception must surface as an immediate error on every
    request of the batch (CellFailure sentinel), not as a 30s timeout,
    and must not kill the batch worker."""

    boom = {"on": True}

    def flaky(qs):
        if boom["on"]:
            raise RuntimeError("boom")
        return _ok_fn(qs)

    cell = ServingCell(flaky, name="flaky", max_wait_ms=0.5)
    try:
        t0 = time.perf_counter()
        with pytest.raises(RuntimeError, match="backend failed"):
            cell.search(np.ones(4, np.float32), timeout=10.0)
        assert time.perf_counter() - t0 < 5.0, "failure took the timeout path"
        assert isinstance(cell.failure(), RuntimeError)
        boom["on"] = False                    # worker survived the raise
        d, _ = cell.search(np.ones(4, np.float32), timeout=5.0)
        assert d.shape == (3,)
    finally:
        cell.close()


def test_cell_close_fails_queued_requests():
    cell = ServingCell(_slow_fn(0.5), name="c", max_wait_ms=0.5,
                       max_batch=1)
    fut1 = cell.submit(np.ones(4, np.float32))
    fut2 = cell.submit(np.ones(4, np.float32))
    cell.close()
    # whatever was still queued at close resolves to CellFailure, so a
    # routed caller re-dispatches instead of waiting out its timeout
    outs = [fut1.get(timeout=6.0), fut2.get(timeout=6.0)]
    assert any(isinstance(o, CellFailure) for o in outs)


# ---------------------------------------------------------------------------
# cell: work-conserving micro-batching (drain the backlog, hold on request)
# ---------------------------------------------------------------------------


class _Gated:
    """A backend whose calls block until ``gate`` is set; ``entered``
    is set once a call is inside, so the worker is known to be busy."""

    def __init__(self):
        self.gate = threading.Event()
        self.entered = threading.Event()

    def __call__(self, qs):
        self.entered.set()
        self.gate.wait(10.0)
        return _ok_fn(qs)


class _HoldProbe(queue.Queue):
    """A cell queue that sets ``holding`` when the worker, having
    drained it empty, blocks on it again: the batch's hold."""

    def __init__(self):
        super().__init__()
        self.holding = threading.Event()
        self._drained_empty = False

    def get(self, block=True, timeout=None):
        if block and self._drained_empty:
            self.holding.set()
        try:
            item = super().get(block, timeout)
        except queue.Empty:
            self._drained_empty = not block
            raise
        self._drained_empty = False
        return item


def _serve_backlog(n, *, cancel=(), **cell_kw):
    """Hold the worker inside the backend on a first request, queue
    ``n`` requests behind it (those at indices ``cancel`` abandoned by
    their caller), release it; returns the cell's batch sizes in order,
    its ``batched_from_backlog`` count and the ``drained`` attribute of
    each ``batch`` span."""
    fn = _Gated()
    tr = Tracer(capacity=1 << 10)
    prev = set_tracer(tr)
    cell = ServingCell(fn, name="c", **cell_kw)
    try:
        q = np.ones(4, np.float32)
        first = cell.submit(q)
        assert fn.entered.wait(10.0)
        futs = []
        for j in range(n):
            gone = threading.Event()
            if j in cancel:
                gone.set()
            futs.append(cell.submit(q, cancelled=gone))
        fn.gate.set()
        first.get(timeout=10.0)
        for j, f in enumerate(futs):
            if j not in cancel:
                f.get(timeout=10.0)
        return (cell.stats().batch_sizes,
                cell.metrics.get("batched_from_backlog").value,
                [e["args"]["drained"] for e in tr.events("batch")])
    finally:
        cell.close()
        set_tracer(prev)


@pytest.mark.parametrize("cell_kw", [{}, {"max_wait_ms": 0.0}],
                         ids=["default", "no-hold"])
def test_cell_drains_the_backlog_into_one_batch(cell_kw):
    """Requests queued while the worker is busy all ride its next batch,
    with no ``max_wait_ms`` and no arrival rate observed yet, without a
    wait for more and without a dispatch of one alone."""
    sizes, _, _ = _serve_backlog(5, max_batch=8, **cell_kw)
    assert sizes == [1, 5]


def test_cell_drain_stops_at_max_batch():
    sizes, from_backlog, drained = _serve_backlog(20, max_batch=8)
    assert sizes == [1, 8, 8, 4]
    assert drained == sizes and from_backlog == 21


def test_cell_counts_the_requests_drained_from_the_backlog():
    """``drained`` on the ``batch`` span and the ``batched_from_backlog``
    counter count the dispatched requests the drain took, the first of
    each collection among them, and not an abandoned one."""
    sizes, from_backlog, drained = _serve_backlog(6, cancel={2},
                                                  max_batch=8)
    assert sizes == [1, 5]
    assert drained == [1, 5]
    assert from_backlog == 6


def test_cell_hold_admits_a_request_submitted_after_the_drain():
    """A positive ``max_wait_ms`` still holds the batch open after the
    drain: a request submitted during the hold joins it, and the hold
    ends once the batch reaches ``max_batch``."""
    tr = Tracer(capacity=1 << 10)
    prev = set_tracer(tr)
    cell = ServingCell(_ok_fn, name="c", max_batch=2, max_wait_ms=60e3)
    probe = cell.q = _HoldProbe()
    try:
        q = np.ones(4, np.float32)
        fa = cell.submit(q)
        assert probe.holding.wait(10.0)
        fb = cell.submit(q)
        fa.get(timeout=10.0)
        fb.get(timeout=10.0)
        assert cell.stats().batch_sizes == [2]
        (span,) = tr.events("batch")
        assert span["args"]["size"] == 2 and span["args"]["drained"] == 1
        assert cell.metrics.get("batched_from_backlog").value == 1
    finally:
        cell.close()
        set_tracer(prev)


def _observed_cell(rate_per_s, serve_s):
    """A cell whose worker has observed ``rate_per_s`` arrivals and a
    ``serve_s`` serve time, with its queue behind a hold probe."""
    cell = ServingCell(_ok_fn, name="c", max_batch=2)
    cell._rate, cell._serve_s = rate_per_s, serve_s
    probe = cell.q = _HoldProbe()
    return cell, probe


def test_cell_holds_a_batch_below_the_arrivals_of_one_serve_time():
    """With no ``max_wait_ms`` the cell holds a batch while it carries
    fewer requests than arrive, at the observed rate, in one serve time:
    a request submitted during that hold joins the batch."""
    cell, probe = _observed_cell(1000.0, 60.0)
    try:
        q = np.ones(4, np.float32)
        fa = cell.submit(q)
        assert probe.holding.wait(10.0)
        fb = cell.submit(q)
        fa.get(timeout=10.0)
        fb.get(timeout=10.0)
        assert cell.stats().batch_sizes == [2]
        assert cell.metrics.get("batched_from_backlog").value == 1
    finally:
        cell.close()


def test_cell_does_not_hold_where_under_one_request_arrives_a_serve_time():
    """Where less than one request arrives in a serve time (light load),
    a batch of one is dispatched at once: answered well inside the 60 s
    a hold would last."""
    cell, _ = _observed_cell(0.01, 60.0)
    try:
        cell.submit(np.ones(4, np.float32)).get(timeout=10.0)
        assert cell.stats().batch_sizes == [1]
    finally:
        cell.close()


def test_cell_observes_its_arrival_rate_and_serve_time():
    """The hold's moving averages follow the worker's own collections:
    requests over the time between their first requests, and the time
    from the end of a hold to the delivery."""
    cell = ServingCell(_ok_fn, name="c")
    cell.close()
    for j in range(200):
        cell._observe(4, 0.01 * j, 0.002)
    assert cell._rate == pytest.approx(400.0, rel=1e-4)
    assert cell._serve_s == pytest.approx(0.002, rel=1e-4)
    for j in range(200, 400):
        cell._observe(1, 0.01 * j, 0.004)
    assert cell._rate == pytest.approx(100.0, rel=1e-4)
    assert cell._serve_s == pytest.approx(0.004, rel=1e-4)


# ---------------------------------------------------------------------------
# router: admission, affinity, hedging, rerouting
# ---------------------------------------------------------------------------


def test_router_admission_sheds_with_retriable_signal():
    gate = threading.Event()
    entered = threading.Event()

    def blocked(qs):
        entered.set()
        gate.wait(60.0)       # generous: a loaded CI box must not let
        return _ok_fn(qs)     # the queue drain before the shed probe

    cell = ServingCell(blocked, name="cell0", max_wait_ms=0.5, max_batch=1)
    router = CellRouter([cell], max_queue_depth=2)
    try:
        threads = [
            threading.Thread(
                target=lambda j=j: router.search(
                    np.full(4, j, np.float32), timeout=90.0),
                daemon=True)
            for j in range(3)]                # 1 in compute + 2 queued
        # the first request must be in compute before the others are
        # admitted, or the third sees a depth of 2 and is shed itself
        threads[0].start()
        assert entered.wait(15.0)
        for t in threads[1:]:
            t.start()
        deadline = time.perf_counter() + 15.0
        while cell.depth() < 2 and time.perf_counter() < deadline:
            time.sleep(0.01)
        assert cell.depth() >= 2, "setup never saturated the queue"
        with pytest.raises(FleetOverloadError) as ei:
            router.search(np.full(4, 99, np.float32), timeout=1.0)
        assert ei.value.retriable is True
        assert router.stats().shed == 1
    finally:
        gate.set()
        for t in threads:
            t.join(timeout=5.0)
        router.close()


def test_router_affinity_is_stable_and_balanced():
    cells = [ServingCell(_ok_fn, name=f"cell{i}", max_wait_ms=0.5)
             for i in range(4)]
    router = CellRouter(cells)
    try:
        rng = np.random.default_rng(0)
        qs = [_query(rng) for _ in range(400)]
        first = [router.preferred_cell(q).name for q in qs]
        again = [router.preferred_cell(q).name for q in qs]
        assert first == again, "affinity not deterministic"
        counts = {n: first.count(n) for n in set(first)}
        assert len(counts) == 4
        assert all(c > 400 / 4 / 3 for c in counts.values()), (
            f"rendezvous badly unbalanced: {counts}")
    finally:
        router.close()


def test_router_affinity_remaps_only_failed_cells_keys():
    """Rendezvous property: when a cell dies, only ITS keys move —
    survivors keep their cache heads."""
    cells = [ServingCell(_ok_fn, name=f"cell{i}", max_wait_ms=0.5)
             for i in range(4)]
    router = CellRouter(cells)
    try:
        rng = np.random.default_rng(1)
        qs = [_query(rng) for _ in range(300)]
        before = [router.preferred_cell(q).name for q in qs]
        with router._lock:
            router._mark_down("cell2", RuntimeError("x"))
        after = [router.preferred_cell(q).name for q in qs]
        for b, a in zip(before, after):
            if b != "cell2":
                assert a == b, "a healthy cell's key moved on failure"
            else:
                assert a != "cell2"
        router.revive("cell2")
        assert [router.preferred_cell(q).name for q in qs] == before
    finally:
        router.close()


def test_router_cross_cell_hedge():
    """A straggling primary mesh must not stall the request: after
    hedge_ms the router duplicates onto a different cell and the fast
    cell's answer wins."""
    cells = [ServingCell(_slow_fn(0.5), name="cell0", max_wait_ms=0.5),
             ServingCell(_ok_fn, name="cell1", max_wait_ms=0.5)]
    router = CellRouter(cells, hedge_ms=30.0)
    try:
        q = _query_for(router, np.random.default_rng(2), "cell0")
        t0 = time.perf_counter()
        d, _ = router.search(q, timeout=10.0)
        elapsed = time.perf_counter() - t0
        assert d.shape == (3,)
        assert elapsed < 0.4, f"hedge did not win: {elapsed:.3f}s"
        assert router.stats().hedge_cell == 1
    finally:
        router.close()


def test_router_reroutes_on_cell_failure():
    def failing(qs):
        raise RuntimeError("dead mesh")

    cells = [ServingCell(failing, name="cell0", max_wait_ms=0.5),
             ServingCell(_ok_fn, name="cell1", max_wait_ms=0.5)]
    router = CellRouter(cells)
    try:
        rng = np.random.default_rng(3)
        q = _query_for(router, rng, "cell0")
        d, _ = router.search(q, timeout=10.0)        # rerouted, not raised
        assert d.shape == (3,)
        st = router.stats()
        assert st.rerouted == 1
        assert "cell0" in router.down_cells()
        # admission now avoids the downed cell entirely
        assert router.preferred_cell(q).name == "cell1"
        # all cells down -> shed with the retriable signal
        with router._lock:
            router._mark_down("cell1", RuntimeError("x"))
        with pytest.raises(FleetOverloadError):
            router.search(q, timeout=1.0)
    finally:
        router.close()


def test_router_zero_lost_requests_under_cell_failure():
    """The fig8 acceptance at test scale: a cell failing mid-stream
    loses NOTHING — every request completes via fail-fast rerouting."""
    switch = threading.Event()

    def flaky(qs):
        if switch.is_set():
            raise RuntimeError("injected failure")
        return _ok_fn(qs)

    cells = [ServingCell(flaky, name="cell0", max_wait_ms=0.5),
             ServingCell(_ok_fn, name="cell1", max_wait_ms=0.5),
             ServingCell(_ok_fn, name="cell2", max_wait_ms=0.5)]
    router = CellRouter(cells, max_queue_depth=64)
    try:
        rng = np.random.default_rng(4)
        queries = [_query(rng) for _ in range(60)]
        ok, errors = [], []

        def client(chunk):
            for q in chunk:
                try:
                    d, _ = router.search(q, timeout=15.0)
                    ok.append(d.shape)
                except Exception as e:       # noqa: BLE001 — counting loss
                    errors.append(e)

        chunks = [queries[i::6] for i in range(6)]
        threads = [threading.Thread(target=client, args=(c,), daemon=True)
                   for c in chunks]
        for t in threads:
            t.start()
        time.sleep(0.02)
        switch.set()                          # cell0 dies mid-stream
        for t in threads:
            t.join(timeout=30.0)
        assert not errors, f"lost {len(errors)} requests: {errors[:3]}"
        assert len(ok) == 60
    finally:
        router.close()


def test_router_search_uses_affinity_cell_cache():
    from repro.adaptive import FrequencyAdmissionCache

    cells = [ServingCell(_ok_fn, name=f"cell{i}", max_wait_ms=0.5,
                         cache=FrequencyAdmissionCache(capacity=32))
             for i in range(2)]
    router = CellRouter(cells)
    try:
        q = _query(np.random.default_rng(5))
        pref = router.preferred_cell(q)
        router.search(q, timeout=5.0)
        router.search(q, timeout=5.0)         # exact repeat: cache hit
        assert pref.cache.hits >= 1
        other = next(c for c in router.cells if c is not pref)
        assert other.cache.hits == 0, "affinity leaked to the other cache"
    finally:
        router.close()


# ---------------------------------------------------------------------------
# leader fan-out
# ---------------------------------------------------------------------------


def test_router_apply_updates_rolls_and_aggregates():
    class _Backend:
        def __init__(self):
            self.applied = []

        def __call__(self, qs):
            return _ok_fn(qs)

        def apply_updates(self, target, delta=None, **kw):
            self.applied.append(delta)
            return {"mode": "delta" if delta is not None else "full",
                    "bytes": 7, "full_bytes": 100, "reason": None}

    class _Target:
        def __init__(self):
            self.pops = 0

        def pop_delta(self):
            self.pops += 1
            return f"manifest-{self.pops}"

    backends = [_Backend() for _ in range(3)]
    cells = [ServingCell(b, name=f"cell{i}", max_wait_ms=0.5)
             for i, b in enumerate(backends)]
    router = CellRouter(cells)
    try:
        target = _Target()
        agg = router.apply_updates(target)
        # leader contract: ONE pop, the SAME manifest to every cell
        assert target.pops == 1
        assert all(b.applied == ["manifest-1"] for b in backends)
        assert agg["mode"] == "delta"
        assert agg["bytes"] == 21 and agg["full_bytes"] == 300
        assert set(agg["cells"]) == {"cell0", "cell1", "cell2"}
        # down cells are skipped, not crashed into
        with router._lock:
            router._mark_down("cell1", RuntimeError("x"))
        agg2 = router.apply_updates(target)
        assert target.pops == 2
        assert agg2["cells"]["cell1"]["mode"] == "skipped"
        assert len(backends[1].applied) == 1
        assert len(backends[0].applied) == 2
        # fleet stats aggregate the republish gauges across cells
        st = router.stats()
        assert st.republished_bytes == 7 * 3 + 7 * 2
    finally:
        router.close()


def test_revive_replays_missed_manifests_before_rejoin():
    """A down cell misses rolling delta fan-outs; revive() must replay
    the merged missed window against the last published target BEFORE
    the cell rejoins — never re-admit it serving a stale index — and
    count the resync in stats()."""
    from repro.core.delta import DeltaManifest

    class _Backend:
        def __init__(self):
            self.applied = []

        def __call__(self, qs):
            return _ok_fn(qs)

        def apply_updates(self, target, delta=None, **kw):
            self.applied.append(delta)
            return {"mode": "delta" if delta is not None else "full",
                    "bytes": 7, "full_bytes": 100, "reason": None}

    def _man(bv, v, bn, n, dirty, tombs=()):
        return DeltaManifest(
            base_version=bv, version=v, base_n=bn, n=n,
            dirty_buckets=np.asarray(dirty, np.int64),
            tombstones=np.asarray(tombs, np.int64))

    backends = [_Backend() for _ in range(3)]
    cells = [ServingCell(b, name=f"cell{i}", max_wait_ms=0.5)
             for i, b in enumerate(backends)]
    router = CellRouter(cells)
    try:
        target = object()
        router.apply_updates(target, delta=_man(0, 1, 10, 10, [0]))
        with router._lock:
            router._mark_down("cell1", RuntimeError("x"))
        # cell1 misses two rolling fan-outs
        router.apply_updates(target, delta=_man(1, 2, 10, 12, [1, 3]))
        router.apply_updates(target, delta=_man(2, 3, 12, 12, [3, 5],
                                                tombs=[7]))
        assert len(backends[1].applied) == 1
        rep = router.revive("cell1")
        # the replay is ONE apply carrying the merged covering window
        assert len(backends[1].applied) == 2
        merged = backends[1].applied[-1]
        assert (merged.base_version, merged.version) == (1, 3)
        assert (merged.base_n, merged.n) == (10, 12)
        assert merged.dirty_buckets.tolist() == [1, 3, 5]
        assert merged.tombstones.tolist() == [7]
        assert rep["mode"] == "delta"
        assert "cell1" not in router.down_cells()
        assert router.stats().resyncs == 1
        # a fan-out with no manifest while down -> full re-place on revive
        with router._lock:
            router._mark_down("cell2", RuntimeError("x"))
        router.apply_updates(target, delta=_man(3, 4, 12, 13, [2]))
        router.apply_updates(target, delta=None)
        router.revive("cell2")
        assert backends[2].applied[-1] is None, "expected forced re-place"
        assert router.stats().resyncs == 2
        # reviving a cell that missed nothing replays nothing
        with router._lock:
            router._mark_down("cell0", RuntimeError("x"))
        n_before = len(backends[0].applied)
        assert router.revive("cell0") is None
        assert len(backends[0].applied) == n_before
        assert router.stats().resyncs == 2
    finally:
        router.close()


def test_maintenance_scheduler_as_fleet_leader():
    """A MaintenanceScheduler pointed at the router IS the fleet
    leader: one drift decision on the shared estimator, one reboost,
    one manifest fanned to every cell, every cell's cache
    invalidated."""
    from repro.adaptive import (
        FrequencyAdmissionCache,
        HostIndexBackend,
        MaintenanceScheduler,
        OnlineLikelihoodEstimator,
    )
    from repro.core.index import SearchIndex
    from repro.core.protocol import IndexSpec
    from repro.core.tree import build_qlbt

    rng = np.random.default_rng(6)
    n, d = 256, 8
    db = rng.normal(size=(n, d)).astype(np.float32)
    p0 = np.full(n, 1.0 / n)
    idx = SearchIndex(spec=IndexSpec(kind="qlbt"), db=db,
                      tree=build_qlbt(db, p0, seed=1), p=p0)
    est = OnlineLikelihoodEstimator(n, reference=p0, halflife=64)
    backends = [HostIndexBackend(idx, k=5) for _ in range(2)]
    cells = [ServingCell(b, name=f"cell{i}", max_wait_ms=0.5,
                         cache=FrequencyAdmissionCache(capacity=16),
                         estimator=est)
             for i, b in enumerate(backends)]
    router = CellRouter(cells)
    sched = MaintenanceScheduler(
        est, idx, engine=router, interval_s=None,
        drift_threshold=0.05, min_observations=32,
        cooldown_observations=1, rebalance=False)
    try:
        gens = [c.cache.generation for c in cells]
        # skew every observation onto a tiny head: drift explodes
        head = np.arange(4)
        for _ in range(40):
            est.observe(head)
        ev = sched.check_now()
        assert ev is not None, "leader never triggered"
        rep = ev["republish"]
        assert set(rep["cells"]) == {"cell0", "cell1"}
        # every cell got the same republished index reference
        assert all(b.index is idx for b in backends)
        assert all(b.last_delta is backends[0].last_delta
                   for b in backends)
        assert all(c.cache.generation == g + 1
                   for c, g in zip(cells, gens)), (
            "a cell's cache survived the fan-out")
    finally:
        sched.close()
        router.close()


# ---------------------------------------------------------------------------
# disjoint submesh partitioning
# ---------------------------------------------------------------------------


def test_make_cell_meshes_single_device_requires_sharing():
    import jax

    from repro.launch.mesh import make_cell_meshes

    if len(jax.devices()) > 1:
        pytest.skip("pool has multiple devices")
    with pytest.raises(RuntimeError, match="share_devices"):
        make_cell_meshes(2)
    meshes = make_cell_meshes(2, share_devices=True)
    assert len(meshes) == 2
    assert all(m.axis_names == ("data",) for m in meshes)
    assert all(m.devices.size == 1 for m in meshes)
    # one cell over the whole pool needs no sharing
    (m,) = make_cell_meshes(1)
    assert m.devices.size == len(jax.devices())


def test_make_cell_meshes_disjoint_blocks():
    """Disjoint partitioning over a fake pool: consecutive blocks, no
    device in two cells."""
    import jax

    from repro.launch.mesh import make_cell_meshes

    devs = list(jax.devices()) * 4           # fake a 4x pool by reuse
    meshes = make_cell_meshes(4, devices=devs, shape=(1,))
    assert len(meshes) == 4
    for i, m in enumerate(meshes):
        assert list(m.devices.ravel()) == devs[i:i + 1]
    with pytest.raises(ValueError):
        make_cell_meshes(0)


def test_build_fleet_cells_one_spec_per_mesh():
    from repro.configs.base import AnnConfig, ShapeSpec
    from repro.launch.cells import build_fleet_cells
    from repro.launch.mesh import make_cell_meshes

    cfg = AnnConfig(name="fleet-test", n=2048, d=32, n_clusters=16,
                    nprobe=4)
    shape = ShapeSpec("serve_sm", "serve", dims={"batch": 8, "k": 10})
    meshes = make_cell_meshes(2, share_devices=True)
    specs = build_fleet_cells(cfg, "ann", meshes, shape)
    assert len(specs) == 2
    for spec, mesh in zip(specs, meshes):
        assert spec.step_fn is not None
        assert spec.in_shardings[0].mesh is mesh
    # replicas are identical up to mesh
    assert specs[0].note == specs[1].note
    assert [a.shape for a in specs[0].args] == \
        [a.shape for a in specs[1].args]


# ---------------------------------------------------------------------------
# stats plumbing
# ---------------------------------------------------------------------------


def test_fleet_stats_and_lat_summary_breakdown():
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.common import lat_summary

    cells = [ServingCell(_ok_fn, name=f"cell{i}", max_wait_ms=0.5)
             for i in range(2)]
    router = CellRouter(cells)
    try:
        rng = np.random.default_rng(7)
        ts = []
        for _ in range(12):
            q = _query(rng)
            t0 = time.perf_counter()
            router.search(q, timeout=5.0)
            ts.append(time.perf_counter() - t0)
        st = router.stats()
        assert st.n == 12
        assert set(st.cells) == {"cell0", "cell1"}
        assert sum(s.n for s in st.cells.values()) == 12
        out = lat_summary(ts, stats=st)
        assert set(out["cells"]) == {"cell0", "cell1"}
        assert all("p99_ms" in v for v in out["cells"].values())
        # zero-valued routing counters stay out of the row; force one in
        router.metrics.counter("rerouted").inc()
        out2 = lat_summary(ts, stats=router.stats())
        assert out2["rerouted"] == 1 and "shed" not in out2
    finally:
        router.close()


def test_build_fleet_shares_one_estimator():
    from repro.adaptive import OnlineLikelihoodEstimator
    from repro.launch.mesh import make_cell_meshes

    rng = np.random.default_rng(8)
    db = rng.normal(size=(128, 8)).astype(np.float32)
    est = OnlineLikelihoodEstimator(128)
    meshes = make_cell_meshes(2, share_devices=True)
    router = build_fleet(meshes, db, kind="brute", k=5,
                         cache_capacity=16, estimator=est,
                         cell_kw=dict(max_wait_ms=0.5))
    try:
        assert len(router.cells) == 2
        assert all(c.estimator is est for c in router.cells)
        caches = [c.cache for c in router.cells]
        assert caches[0] is not caches[1], "caches must be per-cell"
        d, i = router.search(db[0], timeout=30.0)
        assert d.shape == (5,)
        # the worker observes AFTER delivering the result — poll briefly
        deadline = time.perf_counter() + 5.0
        while est.n_total == 0 and time.perf_counter() < deadline:
            time.sleep(0.01)
        assert est.n_total > 0, "shared estimator saw no traffic"
    finally:
        router.close()
