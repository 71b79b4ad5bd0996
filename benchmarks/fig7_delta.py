"""Fig. 7 (beyond paper): delta vs full republish across mutation fractions.

The maintenance loop (fig5 mutations, fig6 reboosts) ends in a republish:
``ShardedSearchBackend.apply_updates`` re-places the mutated index onto
the mesh.  This benchmark measures what PR 5's delta shipping saves: for
each mutation fraction f the same mutated index is republished twice —

  * ``delta`` — ``apply_updates(idx, delta=idx.pop_delta())``: only the
    dirty-bucket slabs (forest), dirty bucket rows (IVF), or appended
    rows + validity mask (brute) cross the host->device boundary, applied
    in place by the jitted fixed-shape scatter;
  * ``full``  — the PR-3 path: every device array re-placed.

Two mutation patterns per fraction:

  * ``clustered`` — deletes drain the fullest buckets and adds land near
    those buckets' centroids (the paper's skewed-arrival regime: new
    things get popular *somewhere*, not everywhere).  This is the regime
    delta shipping targets: the dirty set stays a handful of buckets.
  * ``uniform``   — mutations spread over the whole corpus; at equal f
    they dirty far more buckets, so the delta fraction degrades toward
    (and past) the fallback threshold — reported honestly so the
    operating envelope is visible.

Reported per row: bytes shipped, bytes a full re-place ships, their
ratio (``delta_fraction``), and the apply wall time of both paths.  The
acceptance bound: at f <= 0.10 **clustered**, delta bytes <= 25% of
full.  The last segment routes one republish through ``ServingEngine``
so the ``EngineStats.republished_bytes`` / ``delta_fraction`` gauges
(the counters ``docs/tuning.md`` quotes) appear in the same CSV.

Rows land in ``benchmarks/results/delta.csv`` and on stdout.
"""
from __future__ import annotations

import os
import time

import numpy as np

from benchmarks.common import RESULTS, csv_row, lat_summary


def _mk(rng, centers, n, d):
    return (centers[rng.integers(0, centers.shape[0], n)]
            + rng.normal(size=(n, d))).astype(np.float32)


def _clustered_mutation(idx, rng, n_mut):
    """Delete ~n_mut/2 entities draining the fullest buckets; add the
    same count near those buckets' centroids."""
    half = n_mut // 2
    order = np.argsort(-idx.bucket_counts)
    dele, hot = [], []
    got = 0
    for b in order:
        if got >= half:
            break
        ids = idx.bucket_ids[b][: idx.bucket_counts[b]]
        ids = ids[ids >= 0]
        take = min(ids.size, half - got)
        dele.append(ids[:take].copy())
        hot.append(int(b))
        got += take
    dele = np.concatenate(dele) if dele else np.zeros(0, np.int64)
    idx.delete_entities(dele)
    cents = idx.centroids[rng.choice(hot, half)]
    new = (cents + 0.3 * rng.normal(size=cents.shape)).astype(np.float32)
    idx.add_entities(new)


def _uniform_mutation(idx, rng, n_mut, centers, d):
    half = n_mut // 2
    live = np.nonzero(idx.alive)[0]
    idx.delete_entities(rng.choice(live, half, replace=False))
    idx.add_entities(_mk(rng, centers, half, d))


def _timed_apply(fn, iters=2):
    out = fn()                             # first call pays any jit
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn()
        ts.append(time.perf_counter() - t0)
    return out, float(np.median(ts)) * 1e3


def run(n: int = 20000, d: int = 32, n_clusters: int = 64,
        fractions=(0.01, 0.05, 0.1, 0.3), seed: int = 0) -> list:
    import jax

    from repro.core.two_level import TwoLevelConfig, build_two_level
    from repro.distributed.backend import ShardedSearchBackend
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((jax.device_count(),), ("data",))
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(64, d)) * 4
    rows = []
    cases = [("forest", "tree"), ("ivf", "brute")]
    for kind, bottom in cases:
        for pattern in ("clustered", "uniform"):
            for frac in fractions:
                rng = np.random.default_rng(17)
                db = _mk(rng, centers, n, d)
                cfg = TwoLevelConfig(
                    n_clusters=n_clusters, top="brute", bottom=bottom,
                    kmeans_iters=5, tree_leaf=8)
                idx = build_two_level(db, cfg)
                kw = dict(kind=kind, k=10, axes=("data",),
                          nprobe_local=4, beam_width=8, headroom=1.5)
                beA = ShardedSearchBackend(mesh, idx, **kw)
                beB = ShardedSearchBackend(mesh, idx, **kw)
                n_mut = int(frac * n)
                if pattern == "clustered":
                    _clustered_mutation(idx, rng, n_mut)
                else:
                    _uniform_mutation(idx, rng, n_mut, centers, d)
                man = idx.pop_delta()
                st, t_delta = _timed_apply(
                    lambda: beA.apply_updates(idx, delta=man))
                _, t_full = _timed_apply(lambda: beB.apply_updates(idx))
                row = {
                    "kind": kind, "pattern": pattern, "frac": frac,
                    "mode": st["mode"],
                    "dirty_buckets": int(man.dirty_buckets.size),
                    "bytes": st["bytes"],
                    "full_bytes": st["full_bytes"],
                    "delta_fraction": round(
                        st["bytes"] / max(st["full_bytes"], 1), 4),
                    "t_delta_ms": round(t_delta, 2),
                    "t_full_ms": round(t_full, 2),
                }
                rows.append(row)
                csv_row(
                    f"fig7_{kind}_{pattern}_f{frac}", t_delta * 1e3,
                    f"mode={row['mode']},frac={row['delta_fraction']},"
                    f"dirty={row['dirty_buckets']},"
                    f"bytes={row['bytes']},full={row['full_bytes']},"
                    f"t_full_ms={row['t_full_ms']}")

    # brute kind: append-only growth + tombstones on a raw corpus
    from repro.core.delta import DeltaManifest

    for frac in fractions:
        rng = np.random.default_rng(17)
        db = _mk(rng, centers, n, d)
        beA = ShardedSearchBackend(mesh, db, k=10, axes=("data",),
                                   headroom=1.5)
        beB = ShardedSearchBackend(mesh, db, k=10, axes=("data",),
                                   headroom=1.5)
        half = int(frac * n) // 2
        grown = np.concatenate([db, _mk(rng, centers, half, d)])
        alive = np.ones(grown.shape[0], bool)
        alive[rng.choice(n, half, replace=False)] = False
        man = DeltaManifest(base_version=0, version=1, base_n=n,
                            n=grown.shape[0],
                            tombstones=np.nonzero(~alive)[0])
        st, t_delta = _timed_apply(
            lambda: beA.apply_updates(grown, alive=alive, delta=man))
        _, t_full = _timed_apply(
            lambda: beB.apply_updates(grown, alive=alive))
        row = {"kind": "brute", "pattern": "uniform", "frac": frac,
               "mode": st["mode"], "dirty_buckets": 0,
               "bytes": st["bytes"], "full_bytes": st["full_bytes"],
               "delta_fraction": round(
                   st["bytes"] / max(st["full_bytes"], 1), 4),
               "t_delta_ms": round(t_delta, 2),
               "t_full_ms": round(t_full, 2)}
        rows.append(row)
        csv_row(f"fig7_brute_f{frac}", t_delta * 1e3,
                f"mode={row['mode']},frac={row['delta_fraction']},"
                f"bytes={row['bytes']},full={row['full_bytes']}")

    # acceptance: clustered mutations at f <= 0.1 ship <= 25% of full
    acc = [r for r in rows
           if r["pattern"] == "clustered" and r["frac"] <= 0.1]
    worst = max((r["delta_fraction"] for r in acc), default=0.0)
    csv_row("fig7_summary", 0.0,
            f"worst_delta_fraction_at_10pct={worst:.3f},"
            f"target<=0.25,pass={worst <= 0.25}")

    # engine segment: the SAME counters surface through EngineStats —
    # fig7 and docs/tuning.md quote lat_summary(..., stats=eng.stats())
    engine_row = _engine_segment(mesh, rng, centers, n, d, n_clusters)
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "delta.csv"), "w") as f:
        cols = list(rows[0])
        f.write(",".join(cols) + "\n")
        for r in rows:
            f.write(",".join(str(r[c]) for c in cols) + "\n")
        f.write(f"# summary worst_delta_fraction_at_10pct={worst:.4f} "
                f"pass={worst <= 0.25}\n")
        f.write(f"# engine {engine_row}\n")
    return rows


def _engine_segment(mesh, rng, centers, n, d, n_clusters):
    from repro.core.two_level import TwoLevelConfig, build_two_level
    from repro.serve.engine import ServingEngine

    db = _mk(rng, centers, n, d)
    idx = build_two_level(db, TwoLevelConfig(
        n_clusters=n_clusters, top="brute", bottom="tree",
        kmeans_iters=5, tree_leaf=8))
    eng = ServingEngine.sharded(
        mesh, idx, kind="forest", k=10, axes=("data",), nprobe_local=4,
        beam_width=8, headroom=1.5, max_batch=32, max_wait_ms=1.0)
    try:
        ts = []
        for j in range(64):
            t0 = time.perf_counter()
            eng.search(db[j], timeout=60.0)
            ts.append(time.perf_counter() - t0)
        _clustered_mutation(idx, rng, int(0.05 * n))
        eng.apply_updates(idx)            # pops + ships the delta
        s = lat_summary(ts, stats=eng.stats())
        csv_row("fig7_engine", s["p50_ms"] * 1e3,
                f"republished_bytes={s['republished_bytes']},"
                f"delta_fraction={s['delta_fraction']}")
        return s
    finally:
        eng.close()


if __name__ == "__main__":
    run()
