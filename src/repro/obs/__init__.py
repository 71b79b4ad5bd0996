"""End-to-end observability: metrics registry, request tracing, and
compile/collector profiling.

Three submodules, one per tentpole concern:

* :mod:`repro.obs.metrics` — fixed-footprint counters/gauges/log-scale
  histograms with JSON snapshot + Prometheus text exposition;
* :mod:`repro.obs.trace` — span API with propagated trace ids, a clock
  anchor that puts spans on the wall clock of a device trace, and
  Chrome-trace/Perfetto export;
* :mod:`repro.obs.profile` — compile-event and garbage-collector
  accounting.

See ``docs/observability.md`` for the metric catalog and span taxonomy.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               merge_snapshots, parse_exposition)
from repro.obs.profile import (PROFILE, install_gc_hooks,
                               install_jax_compile_hooks)
from repro.obs.trace import Tracer, get_tracer, set_tracer

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "PROFILE",
    "Tracer",
    "get_tracer",
    "install_gc_hooks",
    "install_jax_compile_hooks",
    "merge_snapshots",
    "parse_exposition",
    "set_tracer",
]
