"""One run of one cell: set-up, the measured window, the check, the line.

``run(cell_name, seed, seconds, trace)`` does everything ``run.py`` prints:

1. set-up — the corpus and query pool from the seed, the index through
   the program's ``build_two_level``, placement through the program's
   ``repro.launch.serve.serve`` (``build_fleet`` -> ``ServingCell`` ->
   ``CellRouter``) and ``warm`` (every pow2 batch bucket up to the cell's
   ``max_batch``), then a short pass of requests through the router;
2. the window — the traffic file's loop drives ``CellRouter.search`` from
   the benchmark's own client threads for ``seconds``; with ``trace`` the
   JAX profiler and a large program tracer record it;
3. the check — after the window, with the program's state freed, every
   answer against the corpus and a seeded sample against the exact
   float64 reference (``bench.reference``);
4. the metrics — each read by its own file under ``bench/metrics``.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

from bench import data, devtrace, reference, spec
from bench import traffic as tr


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips the cell needs."""


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    traffic: dict
    bm: dict
    root: Path


def load_cell(name: str, root: Path = spec.ROOT) -> Cell:
    bm = spec.benchmark(root)
    w = spec.workload(bm, name)
    return Cell(name, w, spec.config(bm, w["config"], root),
                spec.traffic(w["traffic"], root), bm, Path(root))


def device_info(chips: int, require_chip: bool = True) -> dict:
    """The device as JAX reports it; raises :class:`NoChip` where
    ``require_chip`` and there is no TPU or fewer than ``chips``."""
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"need {chips} TPU chip(s); JAX reports "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def use_compile_cache(root: Path) -> None:
    """JAX's persistent compile cache at ``<root>/.jax_cache`` (a fixed
    path: it is part of the cache key), or where
    ``JAX_COMPILATION_CACHE_DIR`` points; every program is cached."""
    import os

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(Path(root) / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


@dataclasses.dataclass
class Deployed:
    db: np.ndarray
    pool: np.ndarray
    router: object
    data_s: float
    build_s: float
    place_s: float
    compile_s: float


def deploy(cell: Cell, seed: int) -> Deployed:
    """Data, index and placement of ``cell`` from ``seed``, warmed."""
    from repro.launch.mesh import make_cell_meshes
    from repro.launch.serve import Deployment, serve, warm

    cfg, idx, srv = cell.config, cell.config["index"], cell.config["serve"]
    t = time.perf_counter()
    db, std = data.corpus(cfg["corpus"], seed)
    p = data.entity_likelihood(db.shape[0], cell.traffic, seed)
    pool = data.query_pool(db, std, p, cell.traffic, seed)
    data_s = time.perf_counter() - t
    t = time.perf_counter()
    target = db
    if idx["kind"] != "brute":
        from repro.core.two_level import TwoLevelConfig, build_two_level

        target = build_two_level(db, TwoLevelConfig(
            n_clusters=idx["n_clusters"], top=idx["top"],
            bottom=idx["bottom"], kmeans_iters=idx["kmeans_iters"],
            kmeans_minibatch=idx["kmeans_minibatch"],
            tree_leaf=idx.get("tree_leaf", 8),
            seed=int(data.host_rng(seed, 5).integers(2**31))),
            p=p if idx["bottom"] == "qlbt" else None)
    build_s = time.perf_counter() - t
    dep = Deployment(kind=idx["kind"], db=db, target=target, queries=pool,
                     nprobe=idx.get("nprobe", 1), data_s=data_s,
                     build_s=build_s)
    t = time.perf_counter()
    meshes = make_cell_meshes(srv["cells"], shape=(srv["chips_per_cell"],))
    router = serve(meshes, dep, k=srv["k"], precision=srv["precision"],
                   max_batch=srv["max_batch"])
    place_s = time.perf_counter() - t
    compile_s = warm(router, db.shape[1])
    # the routed path's first calls pay one-off host costs: pay them here
    tr.closed_loop(router.search, pool, seconds=0.5, clients=16, seed=seed,
                   k=srv["k"])
    return Deployed(db, pool, router, data_s, build_s, place_s, compile_s)


def _backends(router) -> list:
    return [c.search_fn for c in router.cells]


def compile_signatures(router) -> int:
    return sum(b.metrics.get("compile_signatures").value
               for b in _backends(router))


def batch_totals(router) -> tuple[int, float]:
    """(dispatches, requests dispatched) so far, over every cell."""
    hs = [c.metrics.get("batch_size") for c in router.cells]
    return sum(h.count for h in hs), sum(h.sum for h in hs)


def drive(cell: Cell, dep: Deployed, seed: int, seconds: float,
          trace: bool, rate: float | None = None) -> tr.Window:
    """The traffic file's loop against ``CellRouter.search``."""
    tf, k = cell.traffic, cell.config["serve"]["k"]
    if tf["loop"] == "open":
        return tr.open_loop(dep.router.search, dep.pool,
                            rate=rate or tf["rate_per_s"], seconds=seconds,
                            clients=tf["clients"], seed=seed, k=k,
                            trace=trace)
    if tf["loop"] == "closed":
        return tr.closed_loop(dep.router.search, dep.pool, seconds=seconds,
                              clients=tf["clients"], seed=seed, k=k,
                              trace=trace)
    raise ValueError(f"unknown loop kind {tf['loop']!r}")


def peak_memory(count: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:count]]
    return int(max(peaks, default=0))


def run(name: str, seed: int, seconds: float, trace: bool, *,
        root: Path = spec.ROOT, t_start: float | None = None,
        require_chip: bool = True, compile_cache: bool = True,
        log=print) -> dict:
    """One run; returns the result line's object (``checks`` last).
    ``require_chip=False`` and ``compile_cache=False`` let a test drive
    a run on the CPU without touching process-wide JAX settings."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(name, root)
    device = device_info(cell.workload["chips"], require_chip)
    t_device = time.perf_counter() - t_start
    if compile_cache:
        use_compile_cache(Path(root))
    dep = deploy(cell, seed)
    router = dep.router
    sigs0 = compile_signatures(router)
    tracer = old_tracer = None
    if trace:
        from repro.obs.trace import Tracer, set_tracer

        tracer = Tracer(capacity=1 << 21)
        old_tracer = set_tracer(tracer)
    try:
        with devtrace.recording(trace) as rec:
            b0 = batch_totals(router)
            window = drive(cell, dep, seed, seconds, trace)
            b1 = batch_totals(router)
    finally:
        if trace:
            set_tracer(old_tracer)
    setup_s = window.t0 - t_start
    compiles = compile_signatures(router) - sigs0
    device["memory_peak_bytes"] = peak_memory(cell.workload["chips"])
    router.close()
    del dep.router, router
    gc.collect()
    log(f"{name}: seed {seed}, set-up {setup_s:.3f}s (device found at "
        f"{t_device:.3f}s; data {dep.data_s:.3f}s, build "
        f"{dep.build_s:.3f}s, placement {dep.place_s:.3f}s, compile "
        f"{dep.compile_s:.3f}s), {window.n} requests in {seconds}s")
    log(f"compilations in the window: {compiles}")
    if tracer is not None:
        log(f"tracer: {tracer.n_dropped} events dropped")
    if window.errors:
        log(f"lost requests, first errors: {window.errors}")

    t = time.perf_counter()
    numbers, recall = check(cell, dep, window, seed)
    log(f"recall@{cell.config['serve']['k']} against the float64 "
        f"reference: {recall!r} (check took "
        f"{time.perf_counter() - t:.3f}s)")
    log("compared numbers: " + ", ".join(f"{n} {v!r}"
                                         for n, v in numbers.items()))
    limits = cell.config["check"]["limits"]
    correct = reference.verdict(numbers, limits)

    ctx = SimpleNamespace(
        cell=cell, config=cell.config, traffic=cell.traffic,
        window=window, setup_s=setup_s, build_s=dep.build_s,
        spans=tracer.events() if tracer is not None else None,
        batches=(b1[0] - b0[0], b1[1] - b0[1]),
        device=devtrace.reduce(rec["planes"]) if trace else None,
        peaks=spec.peaks(device["kind"], root) if trace and require_chip
        else None)
    metrics = {}
    for m in spec.metrics_of(cell.bm, name, trace):
        mod = spec.reader(m["name"], root)
        value = mod.read(ctx)
        note = mod.describe(ctx) if hasattr(mod, "describe") else None
        if note:
            log(note)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": window.n,
           "failed": int((window.status != tr.OK).sum()),
           "metrics": metrics, "device": device}
    if trace and ctx.device is not None:
        device["busy_s"] = ctx.device["busy_s"]
        device["window_s"] = ctx.device["window_s"]
        out["breakdown"] = {
            "device_ops": [[g, s] for g, s in ctx.device["ops"][:10]],
            "idle_gaps": [[g, s] for g, s in ctx.device["gaps"][:10]]}
    out["checks"] = {n: {"value": numbers[n], "limit": lim}
                     for n, lim in limits.items()}
    return out


def check(cell: Cell, dep: Deployed, window: tr.Window, seed: int):
    """(compared numbers, recall@k) of the window's answers."""
    k = cell.config["serve"]["k"]
    depth = max(k, int(cell.config["check"].get("rest_depth", k)))
    answered = np.flatnonzero(window.status == tr.OK)
    size = min(int(cell.config["check"]["sample"]), answered.size)
    sample = np.sort(data.host_rng(seed, 6).choice(answered, size,
                                                   replace=False))
    truth, _ = reference.exact_topk(dep.db, dep.pool[window.query[sample]],
                                    depth)
    numbers = reference.checks(dep.db, dep.pool, window, sample, truth)
    return numbers, 1.0 - numbers["recall_miss"]
