"""A checkout root holding small cells, for running the harness on the
CPU: the real metric readers and peak table, with configurations and
traffic mixes cut to a size a test run holds."""
import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

CONFIGS = {
    "tiny-ivf": {
        "corpus": {"n": 4096, "d": 128, "metric": "l2",
                   "mixture_clusters": 64, "uint8_range": True,
                   "unit_norm": False},
        "index": {"kind": "ivf", "top": "brute", "bottom": "brute",
                  "n_clusters": 64, "nprobe": 8, "kmeans_iters": 4,
                  "kmeans_minibatch": 4096},
    },
    "tiny-qlbt": {
        "corpus": {"n": 2048, "d": 64, "metric": "l2",
                   "mixture_clusters": 16, "uint8_range": False,
                   "unit_norm": False},
        "index": {"kind": "forest", "top": "brute", "bottom": "qlbt",
                  "n_clusters": 16, "nprobe": 4, "kmeans_iters": 4,
                  "kmeans_minibatch": 2048, "tree_leaf": 8,
                  "beam_width": 8},
    },
}

TRAFFIC = {
    "tiny-open": {"loop": "open", "rate_per_s": 150.0, "clients": 16,
                  "zipf_alpha": 1.0, "noise": 0.05, "pool": 512},
    "tiny-closed": {"loop": "closed", "clients": 8, "zipf_alpha": 1.0,
                    "noise": 0.05, "pool": 512},
}


def make_root(tmp: Path) -> Path:
    """Write the small checkout under ``tmp``; returns its root.  Cells
    are named ``<config>.<traffic>`` for every pair."""
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir(parents=True)
    shutil.copytree(BENCH / "metrics", tmp / "bench" / "metrics",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH / "peaks.json", tmp / "bench" / "peaks.json")
    configs, workloads = [], []
    for name, body in CONFIGS.items():
        cfg = dict(body, name=name, serve={
            "precision": "f32", "k": 10, "max_batch": 16, "cells": 1,
            "chips_per_cell": 1},
            check={"sample": 64, "rest_depth": 20, "limits": {
                "lost": 0, "malformed": 0, "dist_err": 1e-5,
                "top1_miss": 0.5, "rest_miss": 0.3}})
        path = f"bench/configs/{name}.json"
        (tmp / path).write_text(json.dumps(cfg))
        configs.append({"name": name, "source": "test", "file": path,
                        "reduced": [], "why": "test"})
        for mix in TRAFFIC:
            workloads.append({"name": f"{name}.{mix}", "config": name,
                              "traffic": mix, "chips": 1, "why": "test"})
    for mix, body in TRAFFIC.items():
        (tmp / "bench" / "traffic" / f"{mix}.json").write_text(
            json.dumps(body))
    bm = dict(real, configs=configs, workloads=workloads)
    for m in bm["end_to_end"] + bm["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bm))
    return tmp
