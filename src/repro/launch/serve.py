"""Serving launcher for the paper's ANN corpora, through the served stack.

  PYTHONPATH=src python -m repro.launch.serve --arch sift-1m --scale 1.0
  PYTHONPATH=src python -m repro.launch.serve --arch sift-1m --kind brute \\
      --precision int8
  PYTHONPATH=src python -m repro.launch.serve --arch radio-station \\
      --kind forest

Builds the deployment from ``--seed``: a synthetic corpus at the arch's
shape, then what the kind serves — the two-level index with a brute
bottom (``ivf``), with a QLBT bottom boosted by a Zipf query likelihood
(``forest``), or the raw corpus (``brute``, the exact fused scan).  It is
placed through the served stack — ``make_cell_meshes`` -> ``build_fleet``
(one ``ShardedSearchBackend`` per cell, a ``ServingCell`` in front of
each, a ``CellRouter`` over them) — every pow2 batch bucket is compiled,
``--n-requests`` queries are served through the router from concurrent
clients, and recall@k is scored against an exact float64 numpy oracle.
Paper bars: recall@10 > 0.8 and P90 < 80 ms.

``chip_smoke.py`` at the repository root drives these functions on a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

CORPUS = {"radio-station": "radio_station", "sift-1m": "sift",
          "deep-10m": "deep"}
KINDS = ("ivf", "brute", "forest")


def use_checkout_compile_cache() -> str:
    """Point JAX's persistent compile cache at ``<checkout>/.jax_cache``.

    For entry points only (never on import, never in tests).  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already honours it and
    nothing is changed.  The path is fixed because it is part of the
    cache key: a directory that moves never hits.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@dataclasses.dataclass
class Deployment:
    """What one served phase needs: the corpus, what the backend places
    (raw corpus or built index), the request queries, and set-up times."""
    kind: str
    db: np.ndarray
    target: object
    queries: np.ndarray
    nprobe: int
    data_s: float
    build_s: float


def build_deployment(arch: str, kind: str = "ivf", *, scale: float = 1.0,
                     seed: int = 0, n_requests: int = 256,
                     db: np.ndarray | None = None) -> Deployment:
    """Corpus (or the given ``db``), index and queries for one phase."""
    from repro.configs.registry import get_arch
    from repro.core.likelihood import sample_queries, zipf_likelihood
    from repro.core.two_level import TwoLevelConfig, build_two_level
    from repro.data.synthetic import make_corpus, make_queries

    if kind not in KINDS:
        raise ValueError(f"kind must be one of {KINDS}, got {kind!r}")
    cfg, _ = get_arch(arch)
    t0 = time.perf_counter()
    if db is None:
        db = make_corpus(CORPUS[arch], scale=scale, seed=seed)
    n = db.shape[0]
    rng = np.random.default_rng(seed + 1)
    p = None
    if kind == "forest":
        # QLBT boosts toward the traffic it serves: a Zipf likelihood
        # over a seeded permutation of the entities, queries drawn from it
        p = zipf_likelihood(n)[rng.permutation(n)]
        queries, _ = sample_queries(rng, db, p, n_requests)
    else:
        queries = make_queries(db, n_requests, seed=seed + 1)
    data_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    target = db
    if kind != "brute":
        k = max(16, min(cfg.n_clusters, int(cfg.n_clusters * scale)))
        target = build_two_level(db, TwoLevelConfig(
            n_clusters=k, top=cfg.top,
            bottom="qlbt" if kind == "forest" else "brute",
            kmeans_iters=8, kmeans_minibatch=min(n, 64 * k), seed=seed),
            p=p)
    return Deployment(kind=kind, db=db, target=target, queries=queries,
                      nprobe=cfg.nprobe, data_s=data_s,
                      build_s=time.perf_counter() - t0)


def serve(meshes, dep: Deployment, *, k: int = 10, precision: str = "f32",
          max_batch: int = 64):
    """Place ``dep`` on every mesh and put a router over the cells.

    The config's nprobe is the *global* probe count: each of a cell's
    chips probes its own best ``ceil(nprobe / chips)`` buckets.  Installs
    the process's compile and garbage-collector hooks (``repro.obs``),
    so a compile or a full collection while serving shows as a span and
    in ``PROFILE``."""
    from repro.obs import install_gc_hooks, install_jax_compile_hooks
    from repro.serve.fleet import build_fleet

    install_jax_compile_hooks()
    install_gc_hooks()

    backend_kw = {"precision": precision}
    if dep.kind != "brute":
        backend_kw["nprobe_local"] = -(-dep.nprobe // meshes[0].size)
    return build_fleet(meshes, dep.target, kind=dep.kind, k=k,
                       backend_kw=backend_kw,
                       cell_kw={"max_batch": max_batch})


def warm(router, d: int) -> float:
    """Compile every pow2 batch bucket a cell can dispatch, on every
    cell, before traffic; returns the seconds it took."""
    t0 = time.perf_counter()
    for cell in router.cells:
        b = 1
        while b <= cell.max_batch:
            cell.search_fn(np.zeros((b, d), np.float32))
            b *= 2
    return time.perf_counter() - t0


def submit_all(router, queries: np.ndarray, *, clients: int = 32,
               timeout: float = 600.0):
    """Serve every query through the router from ``clients`` concurrent
    callers; returns (dists (B, k), ids (B, k)).

    Raises when any cell's backend failed — a request answered with a
    ``CellFailure`` — even where the router re-dispatched it elsewhere.
    """
    with ThreadPoolExecutor(clients) as ex:
        outs = list(ex.map(lambda q: router.search(q, timeout=timeout),
                           queries))
    failed = {c.name: c.failure() for c in router.cells
              if c.failure() is not None}
    if failed:
        raise RuntimeError(f"cell backends failed: {failed}")
    return (np.stack([np.asarray(o[0]) for o in outs]),
            np.stack([np.asarray(o[1]) for o in outs]))


def exact_topk(db: np.ndarray, queries: np.ndarray, k: int,
               chunk: int = 1 << 16) -> np.ndarray:
    """Exact top-k ids in float64 numpy — the oracle, independent of the
    code under test."""
    q = np.asarray(queries, np.float64)
    qn = np.sum(q * q, axis=1, keepdims=True)
    best_d = np.full((q.shape[0], k), np.inf)
    best_i = np.full((q.shape[0], k), -1, np.int64)
    for s in range(0, db.shape[0], chunk):
        x = np.asarray(db[s:s + chunk], np.float64)
        d2 = qn - 2.0 * (q @ x.T) + np.sum(x * x, axis=1)[None, :]
        kk = min(k, d2.shape[1])
        part = np.argpartition(d2, kk - 1, axis=1)[:, :kk]
        cat_d = np.concatenate(
            [best_d, np.take_along_axis(d2, part, axis=1)], axis=1)
        cat_i = np.concatenate([best_i, part + s], axis=1)
        order = np.argsort(cat_d, axis=1, kind="stable")[:, :k]
        best_d = np.take_along_axis(cat_d, order, axis=1)
        best_i = np.take_along_axis(cat_i, order, axis=1)
    return best_i


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="sift-1m", choices=sorted(CORPUS))
    ap.add_argument("--kind", default="ivf", choices=KINDS)
    ap.add_argument("--precision", default="f32", choices=["f32", "int8"])
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--cells", type=int, default=1)
    ap.add_argument("--n-requests", type=int, default=256)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    use_checkout_compile_cache()
    from repro.core.metrics import recall_at_k
    from repro.launch.mesh import make_cell_meshes

    dep = build_deployment(args.arch, args.kind, scale=args.scale,
                           seed=args.seed, n_requests=args.n_requests)
    print(f"{args.arch} {args.kind}/{args.precision}: corpus "
          f"{dep.db.shape[0]} x {dep.db.shape[1]}, data {dep.data_s:.1f}s, "
          f"build {dep.build_s:.1f}s")
    router = serve(make_cell_meshes(args.cells), dep, k=args.k,
                   precision=args.precision)
    try:
        print(f"compile {warm(router, dep.db.shape[1]):.1f}s")
        _, ids = submit_all(router, dep.queries)
        st = router.stats()
    finally:
        router.close()
    r = recall_at_k(ids, exact_topk(dep.db, dep.queries, args.k))
    print(f"recall@{args.k} = {r:.4f}  p50={st.p50_ms:.1f}ms "
          f"p90={st.p90_ms:.1f}ms p99={st.p99_ms:.1f}ms")
    print(f"paper bars: recall>0.8 {'PASS' if r > 0.8 else 'FAIL'}; "
          f"P90<80ms {'PASS' if st.p90_ms < 80 else 'FAIL'}")


if __name__ == "__main__":
    main()
