"""Observability tier tests: metrics registry correctness (bucket
boundaries, quantile error bounds, thread safety, exposition round-trip),
tracer semantics (nesting, cross-thread spans, bounded ring, the clock
anchor against the JAX profiler's clock), the serving-stack integration
(bounded telemetry after >10k requests, outcome span coverage for
routed/hedged/rerouted/cancelled requests, the worker loop's spans and
their ``seq``, the backend call's launch/wait split), the collector and
compile hooks, and the measured-overhead bound the docs quote."""
import gc
import glob
import json
import math
import os
import threading
import time

import numpy as np
import pytest

from repro.obs import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    PROFILE,
    Tracer,
    install_gc_hooks,
    install_jax_compile_hooks,
    merge_snapshots,
    parse_exposition,
    set_tracer,
)


# ---------------------------------------------------------------------------
# histogram: bucket boundaries and quantile error bounds
# ---------------------------------------------------------------------------


class TestHistogram:
    def test_bucket_boundary_le_semantics(self):
        """Prometheus `le` semantics: a value exactly on an edge lands in
        the bucket whose upper bound IS that edge, not the next one."""
        h = Histogram("t", lo=1.0, hi=1000.0, per_decade=1)
        # edges: [1, 10, 100, 1000] (+overflow)
        for v in (1.0, 10.0, 100.0, 1000.0):
            h.observe(v)
        counts = {float(h.edges[i]): int(c)
                  for i, c in enumerate(h._counts[:-1]) if c}
        assert counts == {1.0: 1, 10.0: 1, 100.0: 1, 1000.0: 1}
        assert int(h._counts[-1]) == 0, "an edge value leaked to overflow"
        h.observe(1000.0001)
        assert int(h._counts[-1]) == 1, "v > hi must land in overflow"
        h.observe(0.5)          # v <= lo clamps into bucket 0
        assert int(h._counts[0]) == 2

    def test_quantiles_match_exact_within_bucket_ratio(self):
        """Approximate quantiles vs numpy's exact ones: the log-bucket
        design guarantees relative error bounded by one bucket ratio
        (10^(1/20) - 1 ~ 12% at per_decade=20) across the range."""
        rng = np.random.default_rng(0)
        xs = np.exp(rng.normal(loc=1.0, scale=1.2, size=20000))  # ms-ish
        h = Histogram("lat")
        for v in xs:
            h.observe(float(v))
        bucket_ratio = 10 ** (1 / 20)
        for q in (0.01, 0.1, 0.5, 0.9, 0.99, 0.999):
            exact = float(np.quantile(xs, q))
            approx = h.quantile(q)
            assert exact / bucket_ratio <= approx <= exact * bucket_ratio, \
                f"q={q}: approx {approx} vs exact {exact}"
        # exact ride-alongs are exact, not approximated
        assert h.count == len(xs)
        assert h.sum == pytest.approx(float(xs.sum()), rel=1e-9)
        assert h.mean() == pytest.approx(float(xs.mean()), rel=1e-9)

    def test_quantile_clamps_to_observed_range(self):
        h = Histogram("t")
        h.observe(7.0)
        assert h.quantile(0.0) == 7.0
        assert h.quantile(1.0) == 7.0
        assert Histogram("empty").quantile(0.5) == 0.0

    def test_nonfinite_observations_dropped(self):
        h = Histogram("t")
        h.observe(float("nan"))
        h.observe(float("inf"))
        h.observe(2.0)
        assert h.count == 1 and h.n_dropped == 2
        assert math.isfinite(h.sum)

    def test_footprint_invariant_under_observations(self):
        h = Histogram("t")
        before = h.footprint_bytes()
        for v in np.geomspace(1e-4, 1e6, 5000):
            h.observe(float(v))
        assert h.footprint_bytes() == before

    def test_merged_sums_counts_and_bounds(self):
        a, b = Histogram("a"), Histogram("b")
        for v in (1.0, 2.0, 3.0):
            a.observe(v)
        for v in (100.0, 200.0):
            b.observe(v)
        m = Histogram.merged("m", [a, b])
        assert m.count == 5
        assert m.sum == pytest.approx(306.0)
        assert m.quantile(0.0) == 1.0 and m.quantile(1.0) == 200.0
        with pytest.raises(ValueError, match="bucket layout"):
            Histogram.merged("x", [a, Histogram("c", lo=1.0, hi=10.0)])


# ---------------------------------------------------------------------------
# thread safety: concurrent writers, no lost updates
# ---------------------------------------------------------------------------


class TestConcurrency:
    N_THREADS = 8
    PER_THREAD = 2000

    def _hammer(self, fn):
        errs = []

        def worker():
            try:
                for i in range(self.PER_THREAD):
                    fn(i)
            except Exception as e:                 # pragma: no cover
                errs.append(e)

        ts = [threading.Thread(target=worker)
              for _ in range(self.N_THREADS)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert errs == []

    def test_counter_no_lost_increments(self):
        c = Counter("hits")
        self._hammer(lambda i: c.inc())
        assert c.value == self.N_THREADS * self.PER_THREAD

    def test_histogram_no_lost_observations(self):
        h = Histogram("lat")
        self._hammer(lambda i: h.observe(1.0 + (i % 7)))
        total = self.N_THREADS * self.PER_THREAD
        assert h.count == total
        assert int(h._counts.sum()) == total

    def test_registry_get_or_create_races_to_one_instance(self):
        reg = MetricsRegistry()
        seen = []

        def worker():
            seen.append(reg.counter("shared"))

        ts = [threading.Thread(target=worker) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert all(c is seen[0] for c in seen)

    def test_registry_kind_clash_raises(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError, match="already registered"):
            reg.histogram("x")


# ---------------------------------------------------------------------------
# exposition round-trip + snapshot merging
# ---------------------------------------------------------------------------


class TestExposition:
    def _populated(self):
        reg = MetricsRegistry()
        reg.counter("requests").inc(42)
        reg.gauge("drift").set(0.125)
        h = reg.histogram("latency_ms")
        for v in (0.5, 2.0, 2.0, 40.0, 900.0):
            h.observe(v)
        return reg

    def test_round_trip(self):
        reg = self._populated()
        back = parse_exposition(reg.exposition(prefix="cell0."))
        assert back["cell0_requests"] == {"type": "counter", "value": 42}
        assert back["cell0_drift"] == {"type": "gauge", "value": 0.125}
        hist = back["cell0_latency_ms"]
        assert hist["type"] == "histogram"
        assert hist["count"] == 5
        assert hist["sum"] == pytest.approx(944.5)
        # cumulative le buckets: monotone, ending at the total count
        cums = [hist["buckets"][k] for k in hist["buckets"]]
        assert cums == sorted(cums) and cums[-1] == 5
        assert "+Inf" in hist["buckets"]

    def test_snapshot_is_json_safe_and_merged(self):
        a, b = self._populated(), MetricsRegistry()
        b.counter("requests").inc(1)
        snap = merge_snapshots({"cell0.": a, "cell1.": b})
        json.dumps(snap)                    # must not raise
        assert snap["cell0.requests"]["value"] == 42
        assert snap["cell1.requests"]["value"] == 1
        assert snap["cell0.latency_ms"]["count"] == 5
        assert snap["cell0.latency_ms"]["p50"] > 0


# ---------------------------------------------------------------------------
# tracer: nesting, ordering, cross-thread spans, bounded ring
# ---------------------------------------------------------------------------


class TestTracer:
    def test_span_nesting_and_trace_id_inheritance(self):
        tr = Tracer(capacity=64)
        with tr.span("route", q=1) as outer:
            with tr.span("dispatch") as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.parent_id == outer.span_id
        evs = tr.events()
        # children close (and emit) before parents
        assert [e["name"] for e in evs] == ["dispatch", "route"]
        d, r = evs
        assert d["args"]["parent"] == r["args"]["span_id"]
        assert d["args"]["trace_id"] == r["args"]["trace_id"]
        # child interval nested inside parent interval
        assert r["ts"] <= d["ts"]
        assert d["ts"] + d["dur"] <= r["ts"] + r["dur"] + 1e-3

    def test_exported_chrome_trace_shape(self, tmp_path):
        tr = Tracer(capacity=64)
        with tr.span("route"):
            tr.instant("hedge-fired", cell="cell0")
        p = tr.export(str(tmp_path / "trace.json"))
        doc = json.load(open(p))
        assert doc["displayTimeUnit"] == "ms"
        by_name = {e["name"]: e for e in doc["traceEvents"]}
        assert by_name["route"]["ph"] == "X"
        assert by_name["route"]["dur"] >= 0
        assert by_name["hedge-fired"]["ph"] == "i"
        assert by_name["hedge-fired"]["args"]["cell"] == "cell0"

    def test_cross_thread_record_span(self):
        """The queue-wait shape: started on the caller thread, recorded
        later by the worker thread under an explicit trace_id."""
        tr = Tracer(capacity=64)
        tid0 = tr.new_trace_id()
        t0 = time.perf_counter()
        done = threading.Event()

        def worker():
            tr.record_span("queue", t0, time.perf_counter(),
                           trace_id=tid0, cell="c0")
            done.set()

        threading.Thread(target=worker).start()
        assert done.wait(5.0)
        (ev,) = tr.events("queue")
        assert ev["args"]["trace_id"] == tid0
        assert ev["tid"] != threading.get_ident()

    def test_ring_is_bounded_and_counts_drops(self):
        tr = Tracer(capacity=16)
        for i in range(100):
            tr.instant("tick", i=i)
        assert len(tr.events()) == 16
        assert tr.n_dropped == 84
        # the ring keeps the newest events
        assert tr.events()[-1]["args"]["i"] == 99

    def test_exception_tags_span_and_reraises(self):
        tr = Tracer(capacity=16)
        with pytest.raises(ValueError):
            with tr.span("boom"):
                raise ValueError("x")
        (ev,) = tr.events("boom")
        assert ev["args"]["error"] == "ValueError"

    def test_disabled_tracer_records_nothing(self):
        tr = Tracer(capacity=16, enabled=False)
        with tr.span("route") as sp:
            sp.set(outcome="ok")            # null span absorbs set()
            tr.instant("tick")
        assert tr.events() == []

    def test_chrome_export_carries_the_clock_anchor(self):
        tr = Tracer(capacity=16)
        before = time.time_ns()
        with tr.span("route"):
            pass
        after = time.time_ns()
        anchor = tr.to_chrome()["otherData"]["clock_anchor"]
        assert anchor == tr.clock_anchor
        (ev,) = tr.events("route")
        wall = anchor["time_ns"] + ev["ts"] * 1e3
        assert wall == tr.wall_ns(ev["ts"])
        assert before - 1e5 <= wall <= after + 1e5

    def test_converted_span_aligns_with_a_profiler_annotation(self,
                                                              tmp_path):
        """A span and a ``TraceAnnotation`` around the same block start
        and end within 100 us of each other once the span is converted
        by the clock anchor and the annotation by the profile's start
        time (the Task Environment plane's ``profile_start_time``).
        Entering and leaving an annotation takes tens of us itself, so
        the best of five blocks is held to the bound."""
        import jax
        from jax.profiler import ProfileData

        tr = Tracer(capacity=16)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("warm-up"):
                time.sleep(0.01)
            for i in range(5):
                with tr.span(f"probe{i}"), \
                        jax.profiler.TraceAnnotation(f"probe{i}"):
                    time.sleep(0.005)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(str(tmp_path), "**",
                                         "*.xplane.pb"), recursive=True)
        start = None
        ann = {}
        for plane in ProfileData.from_file(path).planes:
            for key, value in plane.stats:
                if key == "profile_start_time":
                    start = value
            for line in plane.lines:
                for ev in line.events:
                    ann[ev.name] = (ev.start_ns, ev.duration_ns)
        assert start is not None
        apart = []
        for ev in tr.events():
            a0 = start + ann[ev["name"]][0]
            a1 = a0 + ann[ev["name"]][1]
            apart.append(max(abs(tr.wall_ns(ev["ts"]) - a0),
                             abs(tr.wall_ns(ev["ts"] + ev["dur"]) - a1)))
        assert len(apart) == 5
        assert min(apart) < 1e5, f"{min(apart) / 1e3:.1f} us apart"


# ---------------------------------------------------------------------------
# serving-stack integration: bounded telemetry, outcome span coverage
# ---------------------------------------------------------------------------


def _ok_fn(qs):
    b = qs.shape[0]
    return (np.zeros((b, 3), np.float32),
            np.tile(np.arange(3), (b, 1)).astype(np.int64))


class TestServingIntegration:
    def test_bounded_telemetry_after_10k_requests(self):
        """The PR-9 regression guard: the pre-obs cell grew one float per
        request in `latencies`/`queue_waits` forever; the registry must
        hold a byte-identical footprint from request 1 to request N."""
        from repro.serve.cell import ServingCell

        cell = ServingCell(_ok_fn, name="c0", max_wait_ms=0.0,
                           max_batch=64)
        try:
            q = np.ones(4, np.float32)
            futs = [cell.submit(q) for _ in range(64)]
            for f in futs:
                f.get(timeout=10.0)
            baseline = cell.metrics.footprint_bytes()
            n = 12000
            for _ in range(n // 64):
                futs = [cell.submit(q) for _ in range(64)]
                for f in futs:
                    f.get(timeout=10.0)
            st = cell.stats()
            assert st.n >= 10000
            assert cell.metrics.footprint_bytes() == baseline, \
                "telemetry footprint grew with request count"
            # the sidecar batch log is a bounded deque, not a list
            assert len(cell._recent_batches) <= 100
        finally:
            cell.close()

    def test_outcome_span_coverage(self):
        """The exported trace must carry every request outcome the fleet
        produces: routed (ok), hedged, rerouted, and cancelled — plus the
        pipeline stages admission/queue/batch/dispatch under the same
        trace ids."""
        from repro.serve.cell import ServingCell
        from repro.serve.fleet import CellRouter

        tr = Tracer(capacity=4096)
        prev = set_tracer(tr)
        slow = {"on": False}
        boom = {"on": False}

        def flaky(qs):
            if boom["on"]:
                raise RuntimeError("injected")
            if slow["on"]:
                time.sleep(0.2)
            return _ok_fn(qs)

        cells = [ServingCell(flaky, name="cell0", max_wait_ms=0.5),
                 ServingCell(_ok_fn, name="cell1", max_wait_ms=0.5)]
        router = CellRouter(cells, hedge_ms=40.0)
        try:
            rng = np.random.default_rng(3)
            for _ in range(1000):
                q0 = rng.normal(size=(4,)).astype(np.float32)
                if router.preferred_cell(q0).name == "cell0":
                    break
            else:
                raise AssertionError("no query routed to cell0")
            # routed
            router.search(q0, timeout=5.0)
            # hedged: primary slow past hedge_ms, alternate answers
            slow["on"] = True
            router.search(q0, timeout=5.0)
            # cancelled: nobody answers in time
            with pytest.raises(TimeoutError):
                router.search(q0, timeout=0.01)
            slow["on"] = False
            time.sleep(0.5)      # let cell0's worker finish the slow
            # batch — otherwise the next request hedges (primary still
            # busy) instead of rerouting on the injected failure
            # rerouted: primary raises, router fails over
            boom["on"] = True
            router.search(q0, timeout=5.0)
            boom["on"] = False
            time.sleep(0.4)                  # drain stragglers

            routes = tr.events("route")
            outcomes = {e["args"].get("outcome") for e in routes}
            assert {"ok", "hedged", "cancelled", "rerouted"} <= outcomes
            names = tr.span_names()
            assert {"admission", "queue", "batch", "dispatch",
                    "hedge-cell", "reroute", "cancel"} <= names
            # stage spans tie back to their route's trace id
            ok = next(e for e in routes
                      if e["args"].get("outcome") == "ok")
            stage_tids = {e["args"]["trace_id"]
                          for e in tr.events("dispatch")}
            assert ok["args"]["trace_id"] in stage_tids
        finally:
            set_tracer(prev)
            router.close()

    def test_fleet_snapshot_and_exposition_surface(self):
        from repro.serve.cell import ServingCell
        from repro.serve.fleet import CellRouter

        cells = [ServingCell(_ok_fn, name=f"cell{i}", max_wait_ms=0.5)
                 for i in range(2)]
        router = CellRouter(cells)
        try:
            rng = np.random.default_rng(5)
            for _ in range(8):
                router.search(rng.normal(size=(4,)).astype(np.float32),
                              timeout=5.0)
            snap = router.metrics_snapshot()
            json.dumps(snap)
            lat_keys = [k for k in snap if k.endswith("latency_ms")]
            assert lat_keys and sum(
                snap[k]["count"] for k in lat_keys) == 8
            text = router.exposition()
            back = parse_exposition(text)
            assert any(k.endswith("latency_ms_bucket") or
                       k.endswith("latency_ms") for k in back)
            st = router.stats()
            assert st.stages and st.stages["queue"]["n"] >= 8
        finally:
            router.close()

    def test_worker_spans_share_seq_and_cover_the_worker(self):
        """With a stub backend, every batch's collect/batch/dispatch/
        deliver spans carry the seq its requests' queue spans carry, and
        together they cover >= 95 % of the worker thread's time."""
        from repro.serve.cell import ServingCell

        def slow_fn(qs):
            time.sleep(0.002)
            return _ok_fn(qs)

        tr = Tracer(capacity=1 << 14)
        prev = set_tracer(tr)
        try:
            cell = ServingCell(slow_fn, name="c0", max_batch=8,
                               max_wait_ms=1.0)
            try:
                q = np.ones(4, np.float32)
                for burst in range(30):
                    futs = [cell.submit(q) for _ in range(1 + burst % 11)]
                    for f in futs:
                        f.get(timeout=10.0)
                    time.sleep(0.003)
            finally:
                cell.close()
        finally:
            set_tracer(prev)
        evs = [e for e in tr.events() if e["ph"] == "X"]
        by = {}
        for e in evs:
            by.setdefault(e["name"], []).append(e)
        seqs = {n: [e["args"]["seq"] for e in by[n]]
                for n in ("collect", "batch", "dispatch", "deliver")}
        queued = {e["args"]["seq"] for e in by["queue"]}
        assert len(by["queue"]) == sum(1 + b % 11 for b in range(30))
        for n in ("batch", "dispatch", "deliver"):
            assert sorted(seqs[n]) == sorted(queued), n
        assert queued <= set(seqs["collect"])
        worker = {e["tid"] for e in by["collect"]}
        assert len(worker) == 1
        (wtid,) = worker
        loop = sorted((e["ts"], e["ts"] + e["dur"]) for n in seqs
                      for e in by[n] if e["tid"] == wtid)
        covered, end = 0.0, loop[0][0]
        for a, b in loop:
            if b > end:
                covered += b - max(a, end)
                end = b
        extent = loop[-1][1] - loop[0][0]
        assert covered >= 0.95 * extent, (covered, extent)

    def test_option_groups_of_one_collection_share_its_seq(self):
        """A collection served as two option groups: both dispatches
        carry the collection's seq and their own group, each queue span
        joins the dispatch that served it, the worker's spans never
        overlap, and a collection of cancelled requests still takes a
        seq of its own."""
        from repro.serve.cell import ServingCell

        def fn(qs, **kw):
            time.sleep(0.002)
            return _ok_fn(qs)

        tr = Tracer(capacity=1 << 12)
        prev = set_tracer(tr)
        try:
            cell = ServingCell(fn, name="c0", max_batch=8,
                               max_wait_ms=50.0)
            try:
                q = np.ones(4, np.float32)
                gone = threading.Event()
                gone.set()
                cell.submit(q, cancelled=gone)
                time.sleep(0.1)
                futs = [cell.submit(q), cell.submit(q, mode="hybrid"),
                        cell.submit(q), cell.submit(q, mode="hybrid")]
                for f in futs:
                    f.get(timeout=10.0)
            finally:
                cell.close()
        finally:
            set_tracer(prev)
        collects = [e["args"]["seq"] for e in tr.events("collect")]
        assert len(collects) == len(set(collects)) >= 2
        disp = {(e["args"]["seq"], e["args"]["group"]): e["args"]["size"]
                for e in tr.events("dispatch")}
        assert len(disp) == 2 and {g for _, g in disp} == {0, 1}
        assert len({s for s, _ in disp}) == 1
        joined = {}
        for e in tr.events("queue"):
            key = (e["args"]["seq"], e["args"]["group"])
            joined[key] = joined.get(key, 0) + 1
        assert joined == disp == {k: 2 for k in disp}
        loop = sorted((e["ts"], e["ts"] + e["dur"]) for e in tr.events()
                      if e["ph"] == "X" and e["name"] in
                      ("collect", "batch", "dispatch", "deliver"))
        for (_, end), (start, _) in zip(loop, loop[1:]):
            assert start >= end - 1e-3

    def test_backend_launch_and_wait_nest_inside_kernel(self):
        from repro.distributed.backend import ShardedSearchBackend
        from repro.launch.mesh import make_mesh

        rng = np.random.default_rng(0)
        db = rng.normal(size=(256, 16)).astype(np.float32)
        be = ShardedSearchBackend(make_mesh((1,), ("data",)), db, k=5,
                                  axes=("data",))
        be(db[:4])                                  # compile outside
        tr = Tracer(capacity=64)
        prev = set_tracer(tr)
        try:
            be(db[:4])
        finally:
            set_tracer(prev)
        (kernel,) = tr.events("kernel")
        for name in ("backend.launch", "backend.wait"):
            (ev,) = tr.events(name)
            assert ev["args"]["parent"] == kernel["args"]["span_id"]
            assert kernel["ts"] <= ev["ts"]
            assert ev["ts"] + ev["dur"] <= \
                kernel["ts"] + kernel["dur"] + 1e-3
        launch, wait = tr.events("backend.launch")[0], \
            tr.events("backend.wait")[0]
        assert launch["ts"] + launch["dur"] <= wait["ts"] + 1e-3


# ---------------------------------------------------------------------------
# profiling: compile and collector hooks, overhead bound
# ---------------------------------------------------------------------------


class TestProfiling:
    @staticmethod
    def _count(name):
        m = PROFILE.get(name)
        return 0 if m is None else (m.value if m.kind == "counter"
                                    else m.count)

    def test_full_collection_is_one_gc_span_and_counted(self):
        assert install_gc_hooks() and install_gc_hooks()   # idempotent
        tr = Tracer(capacity=64)
        prev = set_tracer(tr)
        was_enabled = gc.isenabled()
        gc.disable()                # no automatic collection in between
        try:
            n2 = self._count("gc_collections.gen2")
            pauses = self._count("gc_pause_ms")
            gc.collect(2)
        finally:
            if was_enabled:
                gc.enable()
            set_tracer(prev)
        (ev,) = tr.events("gc")
        assert ev["ph"] == "X" and ev["dur"] > 0
        assert ev["args"]["generation"] == 2
        assert ev["args"]["collected"] >= 0
        assert ev["tid"] == threading.get_ident()
        assert self._count("gc_collections.gen2") == n2 + 1
        assert self._count("gc_pause_ms") == pauses + 1
        assert "gc_collections_gen2" in PROFILE.exposition()

    def test_young_collections_are_counted_without_spans(self):
        install_gc_hooks()
        tr = Tracer(capacity=64)
        prev = set_tracer(tr)
        was_enabled = gc.isenabled()
        gc.disable()
        try:
            n0 = self._count("gc_collections.gen0")
            pauses = self._count("gc_pause_ms")
            gc.collect(0)
        finally:
            if was_enabled:
                gc.enable()
            set_tracer(prev)
        assert self._count("gc_collections.gen0") == n0 + 1
        assert self._count("gc_pause_ms") == pauses
        assert tr.events("gc") == []

    def test_a_compile_is_a_span_of_its_duration(self):
        import jax

        assert install_jax_compile_hooks()
        tr = Tracer(capacity=256)
        prev = set_tracer(tr)
        try:
            def oddly_named_fn(x):
                return x * 3 + 1

            jax.jit(oddly_named_fn)(np.arange(7.0)).block_until_ready()
        finally:
            set_tracer(prev)
        spans = [e for e in tr.events("jax-compile")
                 if "oddly_named_fn" in e["args"]["fun"]]
        assert spans and all(e["ph"] == "X" and e["dur"] > 0
                             for e in spans)
        assert "backend_compile_duration" in {e["args"]["stage"]
                                              for e in spans}

    def test_measured_overhead_bound(self):
        """The docs claim sub-10us per traced span / observed sample;
        hold the benchmark to ~50us in CI headroom terms — an order of
        magnitude under the ~1ms serving path it instruments."""
        tr = Tracer(capacity=1024)
        h = Histogram("lat")
        n = 3000
        t0 = time.perf_counter()
        for i in range(n):
            with tr.span("probe"):
                h.observe(1.0 + (i & 7))
        per_iter_us = (time.perf_counter() - t0) / n * 1e6
        assert per_iter_us < 50.0, \
            f"span+observe costs {per_iter_us:.1f}us/iter"
