#!/usr/bin/env python3
"""Find a cell's knee: serve one deployment at several open-loop rates.

  python3 bench/sweep.py --workload sift-ivf.zipf-steady --seed 11 \\
      --seconds 5 --rates 1000,2000,3000,4000

Builds the cell once from ``--seed`` and, for each rate, drives the
cell's traffic mix at that rate for ``--seconds`` and prints one JSON line:
p50 and p99 of the due-to-answer latency over every request (shed and
lost requests count as never answered), the shed and lost counts, and the
median latency of the window's first and last quarters (a backlog that
grows through the window shows as a last quarter far above the first).
The knee is the highest rate with p99 <= 80 ms, nothing shed and no
growing backlog.  List a rate more than once to repeat it: the knee is
the highest rate that passes every repeat, on every seed swept.  Each line
also gives the window's full garbage collections and the longest of them,
which hold the interpreter lock.  Needs the chip the cell names.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests/s")
    args = ap.parse_args()

    import gc

    import numpy as np

    from bench import harness, traffic

    cell = harness.load_cell(args.workload, ROOT)
    harness.device_info(cell.workload["chips"])
    harness.use_compile_cache(ROOT)
    dep = harness.deploy(cell, args.seed)
    print(f"set-up {time.perf_counter() - T_START:.1f}s", flush=True)
    full: list = []
    started: dict = {}

    def on_gc(phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            started["t"] = time.perf_counter()
        else:
            full.append(time.perf_counter() - started["t"])

    gc.callbacks.append(on_gc)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            full.clear()
            w = harness.drive(cell, dep, args.seed, args.seconds, False,
                              rate=rate)
            lat = w.latency_ms()
            q = max(1, w.n // 4)
            print(json.dumps({
                "rate": rate, "n": w.n,
                "p50_ms": traffic.percentile(lat, 50),
                "p99_ms": traffic.percentile(lat, 99),
                "shed": int((w.status == traffic.SHED).sum()),
                "lost": int((w.status == traffic.LOST).sum()),
                "first_quarter_p50_ms": float(np.median(lat[:q])),
                "last_quarter_p50_ms": float(np.median(lat[-q:])),
                "gen_lag_p99_ms": traffic.percentile(
                    (w.sent - w.due) * 1e3, 99),
                "full_gc": len(full),
                "full_gc_max_ms": 1e3 * max(full, default=0.0)}),
                flush=True)
    finally:
        gc.callbacks.remove(on_gc)
        dep.router.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
