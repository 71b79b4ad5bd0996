#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

  python3 bench/run.py --workload sift-ivf.zipf-steady --seed 7 \\
      --seconds 10 --trace 0

Run from the root of a checkout, on a machine that holds the chips the
cell asks for.  The cell's configuration, traffic mix and metric readers
are found by name from ``BENCHMARK.json`` (see ``bench/harness.py``).
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the end-to-end metrics, or with
``--trace 1`` the per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number beside its
limit, which also close standard error.  Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args()

    from bench import harness

    log = lambda msg: print(msg, flush=True)
    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), root=ROOT, t_start=T_START,
                          log=log)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
