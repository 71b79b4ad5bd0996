"""Shard-count scaling of the distributed search subsystem.

Runs the sharded brute / IVF / forest backends at 1, 2, 4 and 8 shards
(fake CPU devices, one subprocess per shard count so XLA_FLAGS takes
effect) and records us/query-batch per backend.  Per-shard work shrinks
with the shard count while the merge stays O(shards * B * k), so the curve
exposes the collective overhead the roofline predicts.  On fake devices
the absolute numbers measure dispatch+merge structure, not real speedup —
the shape of the curve is the deliverable.

The brute kind also runs its int8-footprint variant, the measured
counterpart of the ann-scan rows in ``benchmarks/roofline.py``.

Rows land in ``benchmarks/results/sharded_scaling.csv`` and on stdout via
``common.csv_row``.
"""
from __future__ import annotations

import os
import subprocess
import sys

from benchmarks.common import RESULTS, csv_row

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """
import os, sys, time
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=" + sys.argv[1])
import warnings; warnings.filterwarnings("ignore")
import jax, numpy as np
from repro.core.two_level import TwoLevelConfig, build_two_level
from repro.distributed.backend import ShardedSearchBackend
from repro.launch.mesh import make_mesh

S = int(sys.argv[1]); n = int(sys.argv[2]); nq = int(sys.argv[3])
mesh = make_mesh((S,), ("data",))
rng = np.random.default_rng(0)
c = rng.normal(size=(32, 32)) * 4
db = (c[rng.integers(0, 32, n)] + rng.normal(size=(n, 32))).astype(np.float32)
q = (db[:nq] + rng.normal(size=(nq, 32)) * 0.05).astype(np.float32)
idx_b = build_two_level(db, TwoLevelConfig(
    n_clusters=64, top="brute", bottom="brute", kmeans_iters=4))
idx_f = build_two_level(db, TwoLevelConfig(
    n_clusters=64, top="brute", bottom="tree", kmeans_iters=4, tree_leaf=8))
cases = (("brute", db, {}),
         ("brute_int8", db, {"precision": "int8"}),
         ("ivf", idx_b, {}),
         ("forest", idx_f, {}))
for name, target, extra in cases:
    kind = name.split("_")[0]
    fn = ShardedSearchBackend(mesh, target, kind=kind, k=10,
                              axes=("data",), nprobe_local=4, **extra)
    fn(q)                                   # warm the jit cache
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        fn(q)
        ts.append(time.perf_counter() - t0)
    print(name, sorted(ts)[len(ts) // 2] * 1e6)
"""


def run(shards=(1, 2, 4, 8), n=20000, nq=64) -> None:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    rows = []
    for s in shards:
        r = subprocess.run(
            [sys.executable, "-c", _CHILD, str(s), str(n), str(nq)],
            capture_output=True, text=True, timeout=1200, env=env,
            cwd=_REPO,
        )
        if r.returncode != 0:
            print(f"sharded s={s}: FAILED\n{r.stderr[-2000:]}",
                  file=sys.stderr)
            continue
        for line in r.stdout.strip().splitlines():
            kind, us = line.split()
            rows.append((s, kind, float(us)))
            csv_row(f"sharded_{kind}_s{s}", float(us),
                    f"shards={s},n={n},B={nq}")
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "sharded_scaling.csv"), "w") as f:
        f.write("shards,kind,us_per_batch\n")
        for s, kind, us in rows:
            f.write(f"{s},{kind},{us:.1f}\n")


if __name__ == "__main__":
    run()
