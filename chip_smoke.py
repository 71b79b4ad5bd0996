#!/usr/bin/env python3
"""Serve the two-level index on a TPU through the launcher's entry points,
at the paper's real widths, and check the answers.

  python3 chip_smoke.py             # one chip
  python3 chip_smoke.py --chips 4   # the multi-chip paths, on four chips

One chip (sift-1m: 1M x 128 f32, 8192 buckets, nprobe 32; radio-station:
10K x 256):
  * ivf         two-level index, brute bottom   recall@10 > 0.8
  * brute-f32   exact fused scan                recall@10 >= 0.99
  * brute-int8  int8 fused scan                 recall@10 > 0.9 vs f32 scan
  * forest      QLBT bottom at radio-station    recall@10 > FOREST_FLOOR
Four chips (``--chips 4``), and nothing else:
  * deep-ivf / deep-brute   one backend over a 4-chip ("data",) mesh at
    deep-10m (10M x 96, 32768 buckets, global nprobe 32), same bars
  * router      a router over four one-chip sift-1m cells, whose answers
    must be identical to a one-cell router's

Each phase builds its deployment from ``--seed``, places it through
``make_cell_meshes`` -> ``build_fleet``, compiles every batch bucket, serves
256 requests through the ``CellRouter`` and scores recall@10 against an
exact float64 numpy oracle (``repro.launch.serve.exact_topk``).  A phase
fails when a cell backend failed (a ``CellFailure``), when its compiled
program lacks the Pallas kernel (``tpu_custom_call``), or below its bar.

Exits non-zero, printing no result, when JAX finds no TPU or any phase
fails; otherwise the last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Everything runs in this one process, which holds the chip.
"""
import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

K = 10
FOREST_FLOOR = 0.6


def log(msg: str) -> None:
    print(msg, flush=True)


def check_device(n_chips: int) -> dict:
    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"no TPU: JAX backend is {jax.default_backend()!r}")
    devs = jax.devices()
    if len(devs) < n_chips:
        raise SystemExit(f"need {n_chips} TPU chips, found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def served(name, L, meshes, dep, *, precision="f32", max_batch=64):
    """Place, compile, check for the kernel, serve; returns (d, i)."""
    router = L.serve(meshes, dep, k=K, precision=precision,
                     max_batch=max_batch)
    try:
        compile_s = L.warm(router, dep.db.shape[1])
        for cell in router.cells:
            if "tpu_custom_call" not in cell.search_fn.compiled_text(
                    max_batch):
                raise PhaseFailed(f"{name}: {cell.name} runs no Pallas "
                                  "kernel (tpu_custom_call missing)")
        t0 = time.perf_counter()
        d, i = L.submit_all(router, dep.queries)
        serve_s = time.perf_counter() - t0
    finally:
        router.close()
    if not (d.shape == i.shape == (len(dep.queries), K)
            and np.isfinite(d).all() and (i >= 0).all()):
        raise PhaseFailed(f"{name}: malformed answers")
    log(f"{name}: build {dep.build_s:.2f}s (data {dep.data_s:.2f}s), "
        f"compile {compile_s:.2f}s, {len(dep.queries)} requests "
        f"{serve_s:.2f}s over {len(meshes)} cell(s) x "
        f"{meshes[0].size} chip(s)")
    return d, i


class PhaseFailed(Exception):
    pass


def gate(name: str, value: float, ok: bool, bar: str) -> None:
    log(f"{name}: recall@{K} = {value:.4f} ({bar})")
    if not ok:
        raise PhaseFailed(f"{name}: recall@{K} {value:.4f} misses {bar}")


def run_phases(phases) -> list:
    """Run every phase even after one fails (a chip run is dear: one run
    should report them all); returns the failures."""
    failures = []
    for name, fn in phases:
        try:
            fn()
        except Exception as e:
            traceback.print_exc()
            failures.append(f"{name}: {e!r}")
    return failures


def one_chip(L, seed: int, scale: float) -> list:
    from repro.core.metrics import recall_at_k
    from repro.launch.mesh import make_cell_meshes

    meshes = make_cell_meshes(1)
    sift = L.build_deployment("sift-1m", "ivf", scale=scale, seed=seed)
    brute = L.build_deployment("sift-1m", "brute", seed=seed, db=sift.db)
    truth = L.exact_topk(sift.db, sift.queries, K)
    f32 = {}

    def ivf():
        _, ids = served("ivf", L, meshes, sift)
        r = recall_at_k(ids, truth)
        gate("ivf", r, r > 0.8, "> 0.8")

    def brute_f32():
        _, f32["ids"] = served("brute-f32", L, meshes, brute)
        r = recall_at_k(f32["ids"], truth)
        gate("brute-f32", r, r >= 0.99, ">= 0.99")

    def brute_int8():
        _, ids = served("brute-int8", L, meshes, brute, precision="int8")
        log(f"brute-int8: recall@{K} vs oracle = "
            f"{recall_at_k(ids, truth):.4f}")
        r = recall_at_k(ids, f32["ids"])
        gate("brute-int8", r, r > 0.9, "> 0.9 vs the f32 scan")

    def forest():
        dep = L.build_deployment("radio-station", "forest", scale=scale,
                                 seed=seed)
        _, ids = served("forest", L, meshes, dep)
        r = recall_at_k(ids, L.exact_topk(dep.db, dep.queries, K))
        gate("forest", r, r > FOREST_FLOOR, f"> {FOREST_FLOOR}")

    return run_phases([("ivf", ivf), ("brute-f32", brute_f32),
                       ("brute-int8", brute_int8), ("forest", forest)])


def four_chips(L, seed: int, scale: float) -> list:
    from repro.core.metrics import recall_at_k
    from repro.launch.mesh import make_cell_meshes

    # one backend over a 4-chip ("data",) mesh at deep-10m; the corpus
    # and its oracle are built once, by whichever deep phase runs first
    mesh4 = make_cell_meshes(1, shape=(4,))
    deep = {}

    def deep_corpus(kind):
        dep = L.build_deployment("deep-10m", kind, scale=scale, seed=seed,
                                 db=deep.get("db"))
        if "db" not in deep:
            deep["db"] = dep.db
            deep["truth"] = L.exact_topk(dep.db, dep.queries, K)
        return dep, deep["truth"]

    def deep_ivf():
        dep, truth = deep_corpus("ivf")
        _, ids = served("deep-ivf", L, mesh4, dep)
        r = recall_at_k(ids, truth)
        gate("deep-ivf", r, r > 0.8, "> 0.8")

    def deep_brute():
        dep, truth = deep_corpus("brute")
        _, ids = served("deep-brute", L, mesh4, dep)
        r = recall_at_k(ids, truth)
        gate("deep-brute", r, r >= 0.99, ">= 0.99")

    def router():
        # four one-chip cells behind a router answer as one cell does
        sift = L.build_deployment("sift-1m", "ivf", scale=scale, seed=seed)
        d4, i4 = served("router-4", L, make_cell_meshes(4, shape=(1,)),
                        sift)
        d1, i1 = served("router-1", L, make_cell_meshes(1, shape=(1,)),
                        sift)
        same = float((i4 == i1).all(axis=1).mean())
        same_set = float((np.sort(i4, 1) == np.sort(i1, 1)).all(1).mean())
        log(f"router: {same:.4f} of queries with identical ids "
            f"({same_set:.4f} as sets), max |d4 - d1| = "
            f"{float(np.abs(d4 - d1).max()):.3g}, recall@{K} = "
            f"{recall_at_k(i4, L.exact_topk(sift.db, sift.queries, K)):.4f}")
        if same != 1.0:
            raise PhaseFailed("router: four cells answered differently "
                              "from one")

    return run_phases([("router", router), ("deep-ivf", deep_ivf),
                       ("deep-brute", deep_brute)])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="corpus scale (1.0 = the paper's sizes)")
    args = ap.parse_args()

    device = check_device(args.chips)
    from repro.launch import serve as L

    L.use_checkout_compile_cache()
    log(f"device: {device}")
    t0 = time.perf_counter()
    run = four_chips if args.chips == 4 else one_chip
    failures = run(L, args.seed, args.scale)
    log(f"phases ran in {time.perf_counter() - t0:.1f}s")
    if failures:
        print("FAILED: " + " | ".join(failures), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
