"""Single-replica serving cell: request queue, micro-batcher, latency SLOs.

The paper's deployment target is per-query P90 < 80 ms on-device; the
datacenter deployment batches concurrent queries instead.  A
:class:`ServingCell` is the production shell around one search/scoring
function — the *unit of replication* in the fleet tier
(:mod:`repro.serve.fleet` routes across many cells on disjoint meshes):

  * micro-batching: when the worker takes a batch's first request it
    takes every request already queued, up to ``max_batch``, without
    waiting; requests that arrive while a batch is served queue for the
    next collection, so a slow cycle grows the next batch.  Then, by
    default (``max_wait_ms`` 0), it holds the batch open only while the
    batch holds fewer requests than are due, at the arrival rate the
    worker has observed, in the rest of one serve time (its measured
    time from the end of a hold to delivery), so a light load is
    dispatched at once and a dispatch's fixed cost is not paid for one
    request when the next is due before it would be served.  A positive
    ``max_wait_ms`` instead holds every batch until ``max_wait_ms`` after
    its first request or ``max_batch`` requests.  The batch is padded to
    the next power-of-two bucket so jit caches a handful of shapes;
  * per-request latency tracking (P50/P90/P99, queue vs compute split) in
    a fixed-footprint :class:`repro.obs.metrics.MetricsRegistry` — the
    cell's memory does not grow with traffic — plus spans through
    :mod:`repro.obs.trace` that cover the batch worker's loop: ``collect``
    (blocked waiting for a batch's first request), ``batch``,
    ``dispatch``, ``deliver``, each tagged with the cell's collection
    sequence number ``seq`` and, since a collection is served as one
    dispatch per option set, the option group's index ``group``; every
    request's ``queue`` span carries both, so (cell, seq, group) joins a
    request to its dispatch; ``collect`` and ``dispatch`` also carry
    ``device``, the id of the chip the backend's mesh holds (a tuple of
    ids for a mesh of several), so a device trace pairs each chip with
    its own worker;
  * optional hedged dispatch to a replica after ``hedge_ms`` (straggler
    mitigation inside the cell; the *fleet* hedges onto a different
    cell's mesh instead — see ``CellRouter``);
  * adaptive-serving hooks: an exact-match result cache fronting
    :meth:`ServingCell.search` (invalidated on ``apply_updates``) and a
    likelihood estimator fed the top-1 id of every served query, both
    surfaced through :class:`EngineStats` (see ``repro.adaptive``);
  * cancellation: a request abandoned by its caller (timeout) is dropped
    by the batch worker instead of being computed anyway, and never
    lands in the latency/queue-wait stats;
  * fail-fast failure: a backend exception does not strand the batch —
    every affected request receives a :class:`CellFailure` sentinel so a
    router can re-dispatch it to a healthy cell immediately.

``ServingEngine`` (:mod:`repro.serve.engine`) is the single-replica
alias kept for existing callers.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from collections import deque
from typing import Callable, Optional

import numpy as np

from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import get_tracer

__all__ = ["ServingCell", "EngineStats", "CellFailure"]


@dataclasses.dataclass
class CellFailure:
    """Sentinel future value: the cell's backend raised while computing
    the batch holding this request.  A routed caller (``CellRouter``)
    marks the cell down and re-dispatches; a direct :meth:`search`
    caller gets the underlying error re-raised."""

    cell: str
    error: BaseException


def _opts_extra(filter_spec, mode: str, alpha: float) -> bytes:
    """Cache-key suffix for request options that change the answer
    (filter predicates, search mode, hybrid alpha).  Returns ``b""`` for
    a default semantic unfiltered request so existing cache keys — and
    fleet affinity routing, which shares the digest — are unchanged."""
    if mode == "semantic" and (filter_spec is None or filter_spec.empty):
        return b""
    fkey = (b"" if filter_spec is None or filter_spec.empty
            else filter_spec.key())
    return b"|".join((fkey, mode.encode(),
                      np.float32(alpha).tobytes()))


@dataclasses.dataclass
class _Request:
    query: np.ndarray
    t_enqueue: float
    future: "queue.Queue"
    cancelled: threading.Event
    trace_id: int = 0
    t_batch: float = 0.0
    # request options: ``opts`` is the hashable micro-batch grouping key
    # (empty for a default semantic request — those batch exactly as
    # before); requests with different opts never share a backend call,
    # because one dispatch carries one filter/mode/alpha
    opts: tuple = ()
    filter_spec: "object | None" = None
    mode: str = "semantic"
    alpha: float = 0.5
    q_terms: "np.ndarray | None" = None
    q_weights: "np.ndarray | None" = None


@dataclasses.dataclass
class EngineStats:
    """Read-only view over the cell's metrics registry.

    Constructed fresh by :meth:`ServingCell.stats` from the registry's
    histograms and counters — no field here is live mutable state.
    """

    n: int
    p50_ms: float
    p90_ms: float
    p99_ms: float
    mean_ms: float
    queue_ms: float
    batch_sizes: list
    hedges: int
    # adaptive-serving gauges (0 when no cache/estimator is attached):
    # benchmarks and the maintenance scheduler read this one struct
    # instead of poking engine internals
    cache_hits: int = 0
    cache_misses: int = 0
    drift: float = 0.0
    # republish gauges (apply_updates): bytes actually shipped to the
    # backend(s), and shipped / what-full-re-places-would-have-shipped —
    # 1.0 means every republish was a full re-place, 0.0 means none
    # happened yet.  fig6/fig7 and docs/tuning.md quote these counters.
    republished_bytes: int = 0
    delta_fraction: float = 0.0
    # requests whose caller timed out before a result was computed; they
    # are dropped by the batch worker and excluded from the latency and
    # queue-wait percentiles above
    cancelled: int = 0
    # fleet routing counters (0 on a standalone cell; a CellRouter's
    # stats() fills them so fig8 can attribute p99 to routing decisions)
    shed: int = 0
    rerouted: int = 0
    hedge_cell: int = 0
    # revived-cell replays: fan-outs a down cell missed and had applied
    # (merged manifest or forced full re-place) at CellRouter.revive()
    resyncs: int = 0
    # per-cell breakdown: name -> EngineStats of that cell (None on a
    # standalone cell)
    cells: "dict | None" = None
    # per-stage latency breakdown: stage name (queue/batch/dispatch/
    # kernel/rerank) -> {"n", "p50_ms", "p99_ms", "mean_ms"} from the
    # registry's stage histograms (None when nothing was recorded)
    stages: "dict | None" = None


def _mesh_device(search_fn):
    """The id of the chip ``search_fn``'s mesh holds, a tuple of ids
    where it holds several, or None for a backend without a mesh."""
    mesh = getattr(search_fn, "mesh", None)
    if mesh is None:
        return None
    ids = tuple(int(d.id) for d in mesh.devices.flat)
    return ids[0] if len(ids) == 1 else ids


def _bucket(n: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return b


class ServingCell:
    """search_fn(queries (B, d)) -> (dists (B,k), ids (B,k))."""

    # weight of the newest collection in the hold's moving averages
    _EWMA = 1.0 / 16

    def __init__(
        self,
        search_fn: Callable,
        *,
        name: str = "cell0",
        max_batch: int = 64,
        max_wait_ms: float = 0.0,
        hedge_fn: Optional[Callable] = None,
        hedge_ms: float = 50.0,
        cache=None,
        estimator=None,
    ):
        """``cache`` (repro.adaptive.FrequencyAdmissionCache) fronts
        :meth:`search` with exact-match results and is invalidated by
        :meth:`apply_updates`; ``estimator``
        (repro.adaptive.OnlineLikelihoodEstimator) observes the top-1 id
        of every served query so drift-triggered maintenance can follow
        the live traffic.  In a fleet, the estimator is *shared* across
        cells (one drift decision) while the cache is per-cell (affinity
        routing keeps each cell's head coherent)."""
        self.search_fn = search_fn
        self.name = name
        self.device = _mesh_device(search_fn)
        self.hedge_fn = hedge_fn
        self.hedge_ms = hedge_ms
        self.cache = cache
        self.estimator = estimator
        self.max_batch = max_batch
        self.max_wait = max_wait_ms / 1e3
        self.q: "queue.Queue[_Request]" = queue.Queue()
        # every latency/size series lives in fixed-footprint instruments:
        # observing 10 requests or 10 million costs the same bytes
        self.metrics = MetricsRegistry()
        self._h_latency = self.metrics.histogram("latency_ms")
        self._h_queue = self.metrics.histogram("queue_ms")
        self._h_batch = self.metrics.histogram("batch_ms")
        self._h_dispatch = self.metrics.histogram("dispatch_ms")
        self._h_bsize = self.metrics.histogram("batch_size", lo=1.0,
                                               hi=4096.0)
        # dispatched requests the drain took from the backlog, each
        # collection's first among them; the rest of batch_size's sum
        # joined during a hold, so the two say how often holds engage
        self._c_backlog = self.metrics.counter("batched_from_backlog")
        self._c_hedges = self.metrics.counter("hedges")
        self._c_cancelled = self.metrics.counter("cancelled")
        self._c_repub = self.metrics.counter("republished_bytes")
        self._c_repub_full = self.metrics.counter("republish_full_bytes")
        self._c_est_err = self.metrics.counter("estimator_errors")
        self._c_failures = self.metrics.counter("backend_failures")
        # last-100 batch sizes, kept as a *bounded* deque purely for the
        # EngineStats.batch_sizes compatibility list
        self._recent_batches: deque = deque(maxlen=100)
        self._failure: Optional[BaseException] = None
        # guards the failure slot and the recent-batch deque; metric
        # instruments are internally locked and never need it
        self._stats_lock = threading.Lock()
        self._stop = threading.Event()
        # collection sequence number, written by the worker thread alone
        self._seq = 0
        # moving averages of the worker's own collections, written by
        # the worker alone (see _observe): requests a collection, seconds
        # between collections' first requests, their ratio, and seconds
        # from the end of a hold to its delivery
        self._t_last: Optional[float] = None
        self._n_avg = self._gap_avg = self._rate = self._serve_s = 0.0
        self._worker = threading.Thread(target=self._run, daemon=True)
        self._worker.start()

    # -- registry-backed compatibility counters ------------------------
    @property
    def hedges(self) -> int:
        return self._c_hedges.value

    @property
    def n_cancelled(self) -> int:
        return self._c_cancelled.value

    @property
    def republished_bytes(self) -> int:
        return self._c_repub.value

    @property
    def republish_full_bytes(self) -> int:
        return self._c_repub_full.value

    @property
    def estimator_errors(self) -> int:
        return self._c_est_err.value

    @classmethod
    def sharded(cls, mesh, target, *, kind: str = "auto", k: int = 10,
                axes=("data", "model"), query_axes=(), nprobe_local: int = 2,
                beam_width: int = 8, headroom: float = 1.0,
                **engine_kw) -> "ServingCell":
        """Cell over a mesh-sharded corpus/index.

        Builds a :class:`repro.distributed.backend.ShardedSearchBackend`
        (corpus pre-placed on the mesh, shard_map search jitted once) and
        serves it; ``engine_kw`` passes through to the cell constructor
        (``max_batch``, ``hedge_fn``, ...).  ``headroom`` > 1 reserves
        device-array growth room so later ``apply_updates`` calls (online
        index mutation) keep hitting the jitted search.
        """
        from repro.distributed.backend import ShardedSearchBackend

        fn = ShardedSearchBackend(
            mesh, target, kind=kind, k=k, axes=axes, query_axes=query_axes,
            nprobe_local=nprobe_local, beam_width=beam_width,
            headroom=headroom)
        return cls(fn, **engine_kw)

    def apply_updates(self, target, *, delta="auto", **kw):
        """Swap in a mutated corpus/index without stopping the cell.

        Delegates to the backend's ``apply_updates`` (e.g.
        :class:`repro.distributed.backend.ShardedSearchBackend`): device
        placement happens under the backend's lock, in-flight batches
        finish against the old arrays, later batches see the new ones,
        and the jitted search kernel is reused — no cold (re-compiling)
        batch anywhere in the swap.  A hedge replica is updated too —
        a stale replica would keep serving deleted entities on every
        hedged request, so a hedge_fn without ``apply_updates`` is an
        error rather than a silent staleness hole.

        ``delta="auto"`` pops the target's accumulated
        :class:`repro.core.delta.DeltaManifest` (``pop_delta()``) **once**
        and hands the same manifest to the primary and the hedge replica,
        so both walk the same version chain and a dirty-bucket
        maintenance pass ships only its dirty slices (the backend decides
        delta vs full per manifest).  Pass ``delta=None`` to force a full
        re-place, or an explicit manifest to manage popping yourself —
        the fleet leader does exactly that: one pop, the same manifest
        handed to every cell (manifest application is idempotent and
        superset-safe, see ``repro.core.delta``).
        Returns the primary backend's republish stats dict when it
        provides one (``mode``/``bytes``/``full_bytes``), which also
        feeds :class:`EngineStats`' ``republished_bytes`` /
        ``delta_fraction`` gauges.
        """
        for name, fn in (("search_fn", self.search_fn),
                         ("hedge_fn", self.hedge_fn)):
            if fn is None:
                continue
            if not hasattr(fn, "apply_updates"):
                raise TypeError(
                    f"{name} {type(fn).__name__} has no apply_updates; "
                    "only pre-placed backends support online mutation")
        if delta == "auto":
            delta = (target.pop_delta()
                     if hasattr(target, "pop_delta") else None)
        # legacy backends without a delta kwarg keep working: only pass
        # the manifest when there is one
        dkw = {} if delta is None else {"delta": delta}
        with get_tracer().span("republish", cell=self.name) as sp:
            stats = self.search_fn.apply_updates(target, **dkw, **kw)
            hstats = None
            if self.hedge_fn is not None:
                hstats = self.hedge_fn.apply_updates(target, **dkw, **kw)
            # the counters track bytes shipped to EVERY backend — a hedge
            # replica that fell back to a full re-place must show up even
            # when the primary took the delta path
            for st in (stats, hstats):
                if isinstance(st, dict):
                    self._c_repub.inc(int(st.get("bytes", 0)))
                    self._c_repub_full.inc(int(st.get("full_bytes", 0)))
            if isinstance(stats, dict):
                sp.set(mode=stats.get("mode"),
                       bytes=int(stats.get("bytes", 0)))
        if self.cache is not None:
            # invalidate AFTER the swap: the generation token handed out
            # at miss time stops in-flight pre-swap results from being
            # re-inserted (see FrequencyAdmissionCache.offer)
            self.cache.invalidate_all()
        return stats if isinstance(stats, dict) else None

    # ------------------------------------------------------------------
    def submit(self, query: np.ndarray, *, future: "queue.Queue" = None,
               cancelled: Optional[threading.Event] = None,
               trace_id: int = 0, filter_spec=None, mode: str = "semantic",
               alpha: float = 0.5, q_terms=None,
               q_weights=None) -> "queue.Queue":
        """Enqueue one request; returns the future its result lands in.

        ``future`` lets a router share one result queue between a
        primary and a hedge dispatch on another cell (first responder
        wins); ``cancelled`` is the abandon flag — once set, the batch
        worker drops the request instead of computing it.  ``trace_id``
        threads a router-assigned trace through the worker's spans so
        the queue wait and dispatch of one request share an id.
        ``filter_spec``/``mode``/``alpha``/``q_terms``/``q_weights`` are
        the filtered/hybrid search options (docs/filtering.md); the
        worker micro-batches only requests sharing the same options.
        """
        fut = queue.Queue() if future is None else future
        extra = _opts_extra(filter_spec, mode, alpha)
        self.q.put(_Request(
            query=query, t_enqueue=time.perf_counter(), future=fut,
            cancelled=cancelled if cancelled is not None
            else threading.Event(), trace_id=trace_id,
            opts=(extra,) if extra else (),
            filter_spec=filter_spec, mode=mode, alpha=alpha,
            q_terms=None if q_terms is None
            else np.asarray(q_terms, np.int32).reshape(-1),
            q_weights=None if q_weights is None
            else np.asarray(q_weights, np.float32).reshape(-1)))
        return fut

    def depth(self) -> int:
        """Queued (not yet batched) request count — the router's
        admission-control load signal."""
        return self.q.qsize()

    def failure(self) -> Optional[BaseException]:
        """Last backend exception, or None while healthy."""
        with self._stats_lock:
            return self._failure

    def search(self, query: np.ndarray, timeout: float = 30.0, *,
               filter=None, mode: str = "semantic", alpha: float = 0.5,
               q_terms=None, q_weights=None):
        """Blocking single-query call, fronted by the result cache.

        Raises :class:`TimeoutError` when no result arrives in
        ``timeout`` seconds (worker wedged / search_fn stalled); the
        abandoned request is *cancelled* — the batch worker drops it
        instead of computing it, and it never lands in the latency
        stats.  Cached results are only offered back under the
        generation observed at miss time, so a search that raced an
        ``apply_updates`` can never re-insert a stale result.

        ``filter`` (a :class:`repro.core.metadata.FilterSpec`), ``mode``
        (``"semantic"``/``"lexical"``/``"hybrid"``), ``alpha``, and the
        lexical query operands ``q_terms``/``q_weights`` pass through to
        the backend; they are folded into the cache key, so a filtered
        result can never satisfy an unfiltered request for the same
        query vector (or any other option mix-up).
        """
        tracer = get_tracer()
        key = gen = None
        if self.cache is not None:
            key = self.cache.key_for(query,
                                     _opts_extra(filter, mode, alpha))
            gen = self.cache.generation
            hit = self.cache.get(key)
            if hit is not None:
                if self.estimator is not None:
                    # cache hits ARE head traffic — skipping them would
                    # blind the drift estimator to exactly the queries
                    # the index should stay boosted for
                    try:
                        self.estimator.observe(np.asarray(hit[1])[:1])
                    except Exception:
                        self._c_est_err.inc()
                return hit
        cancelled = threading.Event()
        trace_id = tracer.new_trace_id()
        fut = self.submit(query, cancelled=cancelled, trace_id=trace_id,
                          filter_spec=filter, mode=mode, alpha=alpha,
                          q_terms=q_terms, q_weights=q_weights)
        try:
            out = fut.get(timeout=timeout)
        except queue.Empty:
            cancelled.set()
            self._c_cancelled.inc()
            tracer.instant("cancel", cell=self.name, trace_id=trace_id)
            raise TimeoutError(
                f"search timed out after {timeout}s (batch worker "
                "stalled or search_fn hung)") from None
        if isinstance(out, CellFailure):
            raise RuntimeError(
                f"cell {out.cell!r} backend failed") from out.error
        if self.cache is not None:
            self.cache.offer(key, out, generation=gen)
        return out

    def close(self):
        self._stop.set()
        self._worker.join(timeout=5)
        # a closed cell must not strand queued requests: fail them fast
        # so routed callers re-dispatch instead of timing out
        fail = CellFailure(cell=self.name,
                           error=RuntimeError(f"cell {self.name} closed"))
        while True:
            try:
                self.q.get_nowait().future.put(fail)
            except queue.Empty:
                break

    # ------------------------------------------------------------------
    def _collect(self) -> "tuple[list[_Request], float, int, int]":
        """Returns (batch, t_first, seq, drained): the requests
        collected, the instant the first one was dequeued — the
        micro-batch assembly span runs from t_first to dispatch — the
        collection's sequence number, and how many of the batch's
        leading requests the drain took without waiting (the first
        among them).  The blocking wait for that first request is the
        ``collect`` span; an empty batch means the cell was closed while
        waiting."""
        t_wait = time.perf_counter()
        while True:
            try:
                first = self.q.get(timeout=0.1)
                break
            except queue.Empty:
                if self._stop.is_set():
                    return [], 0.0, -1, 0
        t_first = time.perf_counter()
        seq = self._seq
        self._seq += 1
        get_tracer().record_span("collect", t_wait, t_first,
                                 cell=self.name, seq=seq,
                                 device=self.device)
        batch = [first]
        while len(batch) < self.max_batch:
            try:
                batch.append(self.q.get_nowait())
            except queue.Empty:
                break
        drained = len(batch)
        while len(batch) < self.max_batch:
            if self.max_wait > 0:
                rem = t_first + self.max_wait - time.perf_counter()
            elif self._rate > 0:
                # hold while the batch holds fewer requests than are
                # due, at the observed rate, in the rest of one serve
                # time: till then a request that joins gains more (it
                # skips waiting out this dispatch) than the batch's
                # requests lose by waiting for it
                rem = (t_first + self._serve_s - time.perf_counter()
                       - len(batch) / self._rate)
            else:
                break
            if rem <= 0:
                break
            try:
                batch.append(self.q.get(timeout=rem))
            except queue.Empty:
                break
        return batch, t_first, seq, drained

    def _run(self):
        while not self._stop.is_set():
            collected, t_first, seq, drained = self._collect()
            # requests abandoned by their caller (timeout) are dropped
            # here — computing them anyway would waste backend work AND
            # pollute the latency stats with latencies nobody observed
            live = [not r.cancelled.is_set() for r in collected]
            drained = sum(live[:drained])
            collected = [r for r, ok in zip(collected, live) if ok]
            if not collected:
                continue
            self._c_backlog.inc(drained)
            # one backend dispatch carries one filter/mode/alpha, so a
            # collected batch is served as one group per distinct option
            # set; default semantic requests all share the () group and
            # batch exactly as before
            groups: "dict[tuple, list[_Request]]" = {}
            for r in collected:
                groups.setdefault(r.opts, []).append(r)
            t_held, t_group = time.perf_counter(), t_first
            for group, batch in enumerate(groups.values()):
                t_group = self._serve_batch(batch, t_first, t_group, seq,
                                            group, drained)
            self._observe(len(collected), t_first, t_group - t_held)

    def _observe(self, n: int, t_first: float, serve_s: float):
        """Fold one collection into the moving averages the hold reads:
        its ``n`` requests over the time since the previous collection's
        first request (the arrival rate), and ``serve_s``, the time from
        the end of its hold to its delivery."""
        a = self._EWMA
        if self._t_last is not None:
            self._n_avg += a * (n - self._n_avg)
            self._gap_avg += a * (t_first - self._t_last - self._gap_avg)
            self._rate = self._n_avg / self._gap_avg
        self._t_last = t_first
        self._serve_s += a * (serve_s - self._serve_s)

    def _serve_batch(self, batch: "list[_Request]", t_first: float,
                     t_group: float, seq: int, group: int,
                     drained: int) -> float:
        """Serve one option group of collection ``seq``: its ``batch``
        span starts at ``t_group``, where the group before it (if any)
        was delivered, and carries the collection's ``drained`` count.
        Returns the instant this group was."""
        tracer = get_tracer()
        qs = np.stack([r.query for r in batch])
        b = qs.shape[0]
        bb = _bucket(b)
        if bb > b:
            qs = np.pad(qs, ((0, bb - b), (0, 0)))
        t0 = time.perf_counter()
        # per-request queue waits started on the caller thread and
        # end here, on the worker — the cross-thread recording form
        for r in batch:
            tracer.record_span("queue", r.t_enqueue, t_first,
                               trace_id=r.trace_id, cell=self.name,
                               seq=seq, group=group)
        tracer.record_span("batch", t_group, t0,
                           trace_id=batch[0].trace_id, cell=self.name,
                           size=b, bucket=bb, seq=seq, group=group,
                           drained=drained)
        try:
            with tracer.span("dispatch",
                             trace_id=batch[0].trace_id, cell=self.name,
                             size=b, bucket=bb, seq=seq, group=group,
                             device=self.device):
                result = self._dispatch(qs, self._group_kw(batch, bb))
        except Exception as e:
            # fail fast, keep the worker alive: every request in the
            # batch gets a CellFailure sentinel so a router can
            # re-dispatch it immediately instead of timing out
            with tracer.span("deliver", cell=self.name, seq=seq,
                             group=group, failed=True):
                self._c_failures.inc()
                with self._stats_lock:
                    self._failure = e
                fail = CellFailure(cell=self.name, error=e)
                for r in batch:
                    r.future.put(fail)
            return time.perf_counter()
        t1 = time.perf_counter()
        with tracer.span("deliver", cell=self.name, seq=seq, group=group):
            self._deliver(batch, result, t_first, t0, t1)
        return time.perf_counter()

    def _deliver(self, batch: "list[_Request]", result, t_first: float,
                 t0: float, t1: float):
        d, i = result
        b = len(batch)
        served = [(j, r) for j, r in enumerate(batch)
                  if not r.cancelled.is_set()]   # timed out: drop
        # telemetry BEFORE resolving futures: a caller that read its
        # result and immediately calls stats() must see this batch
        for _, r in served:
            self._h_latency.observe((t1 - r.t_enqueue) * 1e3)
            self._h_queue.observe((t_first - r.t_enqueue) * 1e3)
        self._h_batch.observe((t0 - t_first) * 1e3)
        self._h_dispatch.observe((t1 - t0) * 1e3)
        self._h_bsize.observe(b)
        with self._stats_lock:
            self._recent_batches.append(b)
        for j, r in served:
            r.future.put((np.asarray(d[j]), np.asarray(i[j])))
        if self.estimator is not None and served:
            try:
                top = np.asarray(i)[:b, 0]
                self.estimator.observe(top)
            except Exception:       # telemetry must never kill serving
                self._c_est_err.inc()

    @staticmethod
    def _group_kw(batch: "list[_Request]", bb: int) -> dict:
        """Backend kwargs for one option group: the shared
        filter/mode/alpha plus the stacked per-request lexical operands
        (term rows padded to the group's pow2 slot width with -1/0, the
        bucket's pad queries scoring nothing)."""
        r0 = batch[0]
        if not r0.opts:
            return {}
        kw = {"filter_spec": r0.filter_spec, "mode": r0.mode,
              "alpha": r0.alpha}
        if r0.mode != "semantic" and r0.q_terms is not None:
            slots = _bucket(max(r.q_terms.size for r in batch))
            qt = np.full((bb, slots), -1, np.int32)
            qw = np.zeros((bb, slots), np.float32)
            for j, r in enumerate(batch):
                qt[j, :r.q_terms.size] = r.q_terms
                qw[j, :r.q_weights.size] = r.q_weights
            kw["q_terms"] = qt
            kw["q_weights"] = qw
        return kw

    def _dispatch(self, qs, skw: Optional[dict] = None):
        # plain-callable backends (tests pass lambdas) only ever see the
        # bare positional call; option kwargs are only forwarded when a
        # request actually set them
        call = (self.search_fn if not skw
                else lambda q: self.search_fn(q, **skw))
        if self.hedge_fn is None:
            return call(qs)
        holder: dict = {}
        done = threading.Event()

        def primary():
            out = call(qs)
            holder.setdefault("out", out)
            done.set()

        t = threading.Thread(target=primary, daemon=True)
        t.start()
        if not done.wait(self.hedge_ms / 1e3):
            self._c_hedges.inc()
            get_tracer().instant("hedge-fired", cell=self.name)
            # replica answers the hedge under the same request options
            out = (self.hedge_fn(qs) if not skw
                   else self.hedge_fn(qs, **skw))
            holder.setdefault("out", out)
            done.set()
        done.wait()
        return holder["out"]

    # ------------------------------------------------------------------
    def _stage_stats(self) -> dict:
        """Per-stage latency summaries; kernel/rerank come from the
        backend's own registry when it exposes one."""
        stages = {
            "queue": self._h_queue.stats_dict(),
            "batch": self._h_batch.stats_dict(),
            "dispatch": self._h_dispatch.stats_dict(),
        }
        bm = getattr(self.search_fn, "metrics", None)
        if isinstance(bm, MetricsRegistry):
            for hname, stage in (("kernel_ms", "kernel"),
                                 ("rerank_ms", "rerank")):
                h = bm.get(hname)
                if h is not None and h.count:
                    stages[stage] = h.stats_dict()
        return stages

    def stats(self) -> EngineStats:
        lat = self._h_latency
        hedges = self._c_hedges.value
        cancelled = self._c_cancelled.value
        rb = self._c_repub.value
        rfb = self._c_repub_full.value
        with self._stats_lock:
            batch_sizes = list(self._recent_batches)
        ch = cm = 0
        drift = 0.0
        if self.cache is not None:
            ch, cm = self.cache.hits, self.cache.misses
        if self.estimator is not None:
            drift = float(self.estimator.drift()["tv"])
        frac = rb / rfb if rfb else 0.0
        stages = self._stage_stats()
        if lat.count == 0:
            return EngineStats(0, 0, 0, 0, 0, 0, [], hedges,
                               cache_hits=ch, cache_misses=cm, drift=drift,
                               republished_bytes=rb,
                               delta_fraction=frac, cancelled=cancelled,
                               stages=stages)
        return EngineStats(
            n=lat.count,
            p50_ms=lat.quantile(0.5),
            p90_ms=lat.quantile(0.9),
            p99_ms=lat.quantile(0.99),
            mean_ms=lat.mean(),
            queue_ms=self._h_queue.mean(),
            batch_sizes=batch_sizes,
            hedges=hedges,
            cache_hits=ch,
            cache_misses=cm,
            drift=drift,
            republished_bytes=rb,
            delta_fraction=frac,
            cancelled=cancelled,
            stages=stages,
        )
