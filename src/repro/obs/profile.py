"""Compile and collector profiling hooks.

Three layers, cheapest first:

1. **Compile signatures** (always on) — ``ShardedSearchBackend`` tracks
   the abstract signature (shape x dtype) of every query batch it
   dispatches; the *first* call per signature is the one that paid a
   trace+compile, so its wall time and signature are recorded
   (``compile_signatures`` counter, ``first_call_ms`` histogram, a
   ``compile-signature`` instant in the trace).  A healthy serving cell
   stops accruing signatures after its pow2 warm-up — the same invariant
   ``repro.analysis``'s recompile gate enforces, now observable in
   production telemetry.

2. **JAX monitoring hooks** (:func:`install_jax_compile_hooks`) — JAX
   emits ``/jax/core/compile/...`` duration events when it traces,
   lowers and compiles a function; the listener mirrors each into the
   process-wide :data:`PROFILE` registry and, as a ``jax-compile`` span
   of its measured duration named by the function, into the active
   tracer.

3. **Garbage-collector hooks** (:func:`install_gc_hooks`) — every
   collection is counted in :data:`PROFILE` by generation
   (``gc_collections.gen<n>``); a full (generation-2) collection also
   lands in the ``gc_pause_ms`` histogram and becomes a ``gc`` span on
   the thread that triggered it.  A full collection holds the
   interpreter lock for its whole pause, so every thread of the serving
   path stalls inside it.

Both listeners are idempotent and stay for the process lifetime (JAX
offers no unregister), so they read the *current* default tracer at
event time.
"""
from __future__ import annotations

import gc
import time

from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import get_tracer

__all__ = [
    "PROFILE",
    "install_gc_hooks",
    "install_jax_compile_hooks",
]

# process-wide profiling registry: compile events land here regardless
# of which component triggered them (there is one XLA compiler queue)
PROFILE = MetricsRegistry()

_HOOKS_INSTALLED = False
_GC_HOOK = None
_COMPILE_PREFIX = "/jax/core/compile/"


def install_jax_compile_hooks() -> bool:
    """Mirror JAX's compile-duration monitoring events into
    :data:`PROFILE` and the current default tracer.

    Returns True when the listener is (already) installed, False when
    this jax build has no monitoring surface.  Idempotent — JAX offers
    no per-listener unregister, so exactly one process-wide listener is
    ever added and it routes through module state.
    """
    global _HOOKS_INSTALLED
    if _HOOKS_INSTALLED:
        return True
    try:
        from jax import monitoring
    except ImportError:                       # pragma: no cover
        return False
    if not hasattr(monitoring, "register_event_duration_secs_listener"):
        return False                          # pragma: no cover

    n_events = PROFILE.counter("jax_compile_events")
    h_ms = PROFILE.histogram("jax_compile_ms", lo=1e-2, hi=1e6)

    def _on_duration(event: str, secs: float, **kw) -> None:
        # tracing, lowering and the XLA compile; a persistent-cache hit's
        # "/jax/compilation_cache/compile_time_saved_sec" is no compile
        if not event.startswith(_COMPILE_PREFIX):
            return
        t1 = time.perf_counter()
        n_events.inc()
        h_ms.observe(secs * 1e3)
        get_tracer().record_span(
            "jax-compile", t1 - secs, t1,
            stage=event[len(_COMPILE_PREFIX):],
            fun=str(kw.get("fun_name", "")))

    monitoring.register_event_duration_secs_listener(_on_duration)
    _HOOKS_INSTALLED = True
    return True


def install_gc_hooks() -> bool:
    """Count every garbage collection in :data:`PROFILE` and record each
    full one as a ``gc`` span.

    Counters ``gc_collections.gen0`` / ``.gen1`` / ``.gen2`` count the
    collections of each generation; ``gc_pause_ms`` holds the pause of
    each generation-2 collection.  Only generation 2 gets a span (with
    ``generation`` and ``collected`` attributes), so the young
    collections, hundreds a second under load, do not flood the ring.
    Idempotent: returns True once the callback is in ``gc.callbacks``.

    The callback runs inside whatever allocation triggered the
    collection, on that thread: it touches only instruments resolved
    here and the tracer, whose lock is re-entrant.
    """
    global _GC_HOOK
    if _GC_HOOK is not None:
        return True
    counts = [PROFILE.counter(f"gc_collections.gen{g}") for g in range(3)]
    h_pause = PROFILE.histogram("gc_pause_ms")
    started = [0.0]
    # bound here: a collection at interpreter exit may find this
    # module's globals already cleared
    clock, tracer = time.perf_counter, get_tracer

    def _on_gc(phase: str, info: dict) -> None:
        if phase == "start":
            started[0] = clock()
            return
        gen = info["generation"]
        counts[gen].inc()
        if gen == 2:
            t1 = clock()
            h_pause.observe((t1 - started[0]) * 1e3)
            tracer().record_span("gc", started[0], t1, generation=gen,
                                 collected=info["collected"])

    gc.callbacks.append(_on_gc)
    _GC_HOOK = _on_gc
    return True

