"""Filtered + hybrid search: recall and latency vs filter selectivity.

Sweeps a metadata predicate from selectivity 1.0 (admits everything)
down to 0.01 over the brute, IVF and forest sharded backends, measuring
us/query-batch and recall@k against the pure-numpy filtered oracle.
Filters are compiled to mask *operands* (same shapes, same jit
signature), so the latency column shows the true marginal cost of
filtering — mask AND + the same scan — rather than a recompile.

The interesting curve is the approximate backends at low selectivity:
bucket/beam candidate generation is filter-blind, so a 1% predicate
leaves few admissible candidates per probe and recall sags — the
tuning guidance in ``docs/filtering.md`` (raise nprobe / fall back to
brute under ~5%) quotes these rows.

Hybrid rows run the fused ``alpha * semantic + (1-alpha) * lexical``
combiner on the brute backend at the same selectivities, so the cost of
carrying the BM25 slab scan shows up next to the dense-only rows.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import clustered_corpus, csv_row

SELS = ((1.0, (0, 99)), (0.5, (0, 49)), (0.2, (0, 19)),
        (0.05, (0, 4)), (0.01, (0, 0)))


def _recall(ids, oracle_ids):
    hits = want = 0
    for a, b in zip(np.asarray(ids), np.asarray(oracle_ids)):
        real = set(b[b >= 0].tolist())
        want += len(real)
        hits += len(set(a[a >= 0].tolist()) & real)
    return hits / max(1, want)


def run(n: int = 20000, nq: int = 64, k: int = 10) -> None:
    from repro.core.lexical import build_lexical_slabs, query_operands
    from repro.core.metadata import FilterSpec, MetadataTable
    from repro.core.two_level import TwoLevelConfig, build_two_level
    from repro.distributed.backend import ShardedSearchBackend
    from repro.launch.mesh import make_mesh

    rng = np.random.default_rng(0)
    db = clustered_corpus(rng, n, 32)
    q = (db[rng.integers(0, n, nq)]
         + 0.05 * rng.normal(size=(nq, 32))).astype(np.float32)
    meta = MetadataTable({"pct": (rng.permutation(n) % 100)
                          .astype(np.int32)})
    nv = 500
    docs = [list(rng.integers(0, nv, 8)) for _ in range(n)]
    slabs = build_lexical_slabs(docs, nv, slots=8)
    qt, qw = query_operands(
        [list(rng.integers(0, nv, 4)) for _ in range(nq)], slabs)

    mesh = make_mesh((1,), ("data",))
    kc = max(16, int(np.sqrt(n)))
    idx_i = build_two_level(db, TwoLevelConfig(
        n_clusters=kc, top="brute", bottom="brute", kmeans_iters=4),
        metadata=meta)
    idx_f = build_two_level(db, TwoLevelConfig(
        n_clusters=kc, top="brute", bottom="tree", kmeans_iters=4,
        tree_leaf=8), metadata=meta)
    kw = dict(k=k, axes=("data",), beam_width=8)
    backends = (
        ("brute", ShardedSearchBackend(mesh, db, metadata=meta,
                                       lexical=slabs, **kw)),
        ("ivf", ShardedSearchBackend(mesh, idx_i, nprobe_local=8, **kw)),
        ("forest", ShardedSearchBackend(mesh, idx_f, nprobe_local=8,
                                        **kw)),
    )

    d2 = ((q[:, None, :] - db[None, :, :]) ** 2).sum(-1)

    def oracle_ids(emask):
        dd = np.where(emask[None, :], d2, np.inf)
        oi = np.argsort(dd, axis=1, kind="stable")[:, :k]
        return np.where(np.isinf(np.take_along_axis(dd, oi, 1)), -1, oi)

    def timed_median(fn, iters=5):
        fn()                                      # warm the jit cache
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2] * 1e6

    for sel, (lo, hi) in SELS:
        fs = FilterSpec.range("pct", lo, hi)
        emask = fs.mask(meta, n)
        oi = oracle_ids(emask)
        for name, be in backends:
            us = timed_median(lambda: be(q, filter_spec=fs))
            _, ids = be(q, filter_spec=fs)
            csv_row(f"filtered_{name}_sel{sel}", us,
                    f"recall={_recall(ids, oi):.3f},sel={sel},"
                    f"n={n},B={nq},k={k}")
        # hybrid at the same selectivity (brute backend, alpha=0.5)
        be = backends[0][1]
        us = timed_median(lambda: be(
            q, filter_spec=fs, mode="hybrid", alpha=0.5,
            q_terms=qt, q_weights=qw))
        csv_row(f"filtered_hybrid_sel{sel}", us,
                f"alpha=0.5,sel={sel},n={n},B={nq},k={k}")

    # unfiltered baselines: the marginal cost of the mask AND
    for name, be in backends:
        us = timed_median(lambda: be(q))
        csv_row(f"filtered_{name}_nofilter", us, f"n={n},B={nq},k={k}")


if __name__ == "__main__":
    run()
