#!/usr/bin/env python3
"""The comparison's control, and its faults: answers that must come out
not correct.

  python3 bench/control.py --workload sift-ivf.zipf-steady \\
      --seeds 101,102,103 [--fault bf16,altered,rest_altered,half_batch]

For each seed: the cell's corpus and query pool as a run makes them, and
a sample of the pool of the size a run checks, answered in the program's
place by

* ``bf16`` (the control): exact search with bfloat16 operands and
  float32 accumulation (``reference.control_topk``, on the default
  device), the reference one precision below the configuration's;
* ``altered``: the exact answers with every id moved one row on, its
  distance left as scored — an answer altered where it is produced;
* ``rest_altered``: the exact nearest row kept first, the rows after it
  moved one row on and re-ranked by their exact distances — a search that
  finds the nearest entity and returns well-formed wrong rows behind it;
* ``half_batch``: the exact answers, where the second half of every
  batch of ``max_batch`` gets the first half's — half of the batch left
  out.

Each is judged by the very checks and limits a run applies.  Prints one
JSON line per seed with every compared number, its limit and
``correct``; exits non-zero where any seed's answers come out correct.
Not part of a benchmark run.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


FAULTS = ("bf16", "altered", "rest_altered", "half_batch")


def control_numbers(cell, seed: int, fault: str = "bf16") -> dict:
    """The compared numbers of ``fault``'s answers for ``seed``."""
    return faults_numbers(cell, seed, [fault])[fault]


def faults_numbers(cell, seed: int, faults) -> dict:
    """{fault: compared numbers} for ``seed``: the corpus, pool, sample
    and exact ranking are made once and every fault answers them."""
    import numpy as np

    from bench import data, reference

    k = cell.config["serve"]["k"]
    db, std = data.corpus(cell.config["corpus"], seed)
    p = data.entity_likelihood(db.shape[0], cell.traffic, seed)
    pool = data.query_pool(db, std, p, cell.traffic, seed)
    size = min(int(cell.config["check"]["sample"]), pool.shape[0])
    q = pool[data.host_rng(seed, 6).choice(pool.shape[0], size,
                                           replace=False)]
    depth = max(k, int(cell.config["check"].get("rest_depth", k)))
    ranking, ranking_d = reference.exact_topk(db, q, depth)
    truth, exact_d = ranking[:, :k], ranking_d[:, :k]
    out = {}
    for fault in faults:
        if fault == "bf16":
            dists, ids = reference.control_topk(db, q, k)
        elif fault == "altered":
            dists, ids = exact_d, (truth + 1) % db.shape[0]
        elif fault == "rest_altered":
            ids = truth.copy()
            ids[:, 1:] = (ids[:, 1:] + 1) % db.shape[0]
            dists = np.sum((db[ids].astype(np.float64)
                            - q[:, None, :].astype(np.float64)) ** 2, axis=2)
            order = np.argsort(dists[:, 1:], axis=1, kind="stable")
            ids[:, 1:] = np.take_along_axis(ids[:, 1:], order, axis=1)
            dists[:, 1:] = np.take_along_axis(dists[:, 1:], order, axis=1)
        elif fault == "half_batch":
            dists, ids = exact_d.copy(), truth.copy()
            b = cell.config["serve"]["max_batch"]
            for s in range(0, size, b):
                h = (min(b, size - s) + 1) // 2
                m = min(b, size - s) - h
                dists[s + h:s + h + m] = dists[s:s + m]
                ids[s + h:s + h + m] = ids[s:s + m]
        else:
            raise ValueError(f"unknown fault {fault!r}")
        dists = np.asarray(dists, np.float32)
        bad, err = reference.answer_checks(db, q, dists, ids)
        top1, miss = reference.sample_checks(ids, truth)
        out[fault] = {"lost": 0, "malformed": bad, "dist_err": err,
                      "top1_miss": top1, "recall_miss": miss,
                      "rest_miss": reference.rest_miss(ids, ranking)}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault", default="bf16",
                    help="comma-separated, of " + ", ".join(FAULTS))
    args = ap.parse_args()
    faults = args.fault.split(",")
    if set(faults) - set(FAULTS):
        ap.error(f"unknown fault in {args.fault!r}")

    from bench import harness, reference

    cell = harness.load_cell(args.workload, ROOT)
    limits = cell.config["check"]["limits"]
    passed = []
    for seed in (int(s) for s in args.seeds.split(",")):
        for fault, numbers in faults_numbers(cell, seed, faults).items():
            ok = reference.verdict(numbers, limits)
            passed.append(ok)
            print(json.dumps({"seed": seed, "fault": fault,
                              "correct": ok, "numbers": numbers,
                              "checks": {
                n: {"value": numbers[n], "limit": lim}
                for n, lim in limits.items()}}), flush=True)
    return 1 if any(passed) else 0


if __name__ == "__main__":
    sys.exit(main())
