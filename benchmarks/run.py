"""Benchmark harness: one module per paper table/figure + roofline.

  python -m benchmarks.run             # default (CPU-sized) tiers
  python -m benchmarks.run --full      # paper-scale corpora (slow)
  python -m benchmarks.run --only fig1,roofline

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.csv_row)
and writes each figure's rows to ``benchmarks/results/BENCH_<fig>.json``
(numbers + run config + git sha) so a perf trajectory accumulates across
commits.
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks import common


def _figure(name: str, config: dict, fn) -> None:
    """Run one figure with BENCH_<name>.json recording around it."""
    common.begin_figure(name)
    try:
        fn()
    except BaseException:
        common.finish_figure(config=dict(config, aborted=True))
        raise
    path = common.finish_figure(config=config)
    if path:
        print(f"wrote {path}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale corpora (1M SIFT / 10M DEEP)")
    ap.add_argument("--only", default=None,
                    help="comma list: fig1,table1,fig2d,fig3,sharded "
                         "(alias: fig4),updates,adaptive,delta,fig8,"
                         "fig9,roofline")
    ap.add_argument("--ci", action="store_true",
                    help="CI-sized configs: tiny corpora/shard counts so "
                         "the fast job can persist BENCH_*.json artifacts")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    from repro.launch.serve import use_checkout_compile_cache

    use_checkout_compile_cache()

    def want(name):
        return only is None or name in only

    t0 = time.time()
    if want("fig1"):
        from benchmarks import fig1_qlbt

        _figure("fig1", {"full": args.full}, fig1_qlbt.run)
    if want("table1"):
        from benchmarks import table1_twolevel

        scale = 1.0 if args.full else 0.2
        _figure("table1", {"full": args.full, "scale": scale},
                lambda: table1_twolevel.run(scale=scale))
    if want("fig2d"):
        from benchmarks import fig2d_deep

        scale = 1.0 if args.full else 0.1
        _figure("fig2d", {"full": args.full, "scale": scale},
                lambda: fig2d_deep.run(scale=scale))
    if want("fig3"):
        from benchmarks import fig3_protocol

        _figure("fig3", {"full": args.full}, fig3_protocol.run)
    if want("sharded") or want("fig4"):
        from benchmarks import fig4_sharded

        if args.ci:
            shards, n = (1, 2), 4096
        elif args.full:
            shards, n = (1, 2, 4, 8), 100_000
        else:
            shards, n = (1, 2, 4), 20_000
        _figure("fig4_sharded", {"full": args.full, "ci": args.ci,
                                 "shards": shards, "n": n},
                lambda: fig4_sharded.run(shards=shards, n=n))
    if want("updates"):
        from benchmarks import fig5_updates

        n = 100_000 if args.full else 20_000
        _figure("fig5_updates", {"full": args.full, "n": n},
                lambda: fig5_updates.run(n=n))
    if want("adaptive"):
        from benchmarks import fig6_adaptive

        n = 20_000 if args.full else 8192
        _figure("fig6_adaptive", {"full": args.full, "n": n},
                lambda: fig6_adaptive.run(n=n))
    if want("delta"):
        from benchmarks import fig7_delta

        n = 100_000 if args.full else 20_000
        _figure("fig7_delta", {"full": args.full, "n": n},
                lambda: fig7_delta.run(n=n))
    if want("fig8"):
        from benchmarks import fig8_fleet

        n = 20_000 if args.full else 8192
        sizes = (2, 4, 8)
        _figure("fig8", {"full": args.full, "n": n,
                         "fleet_sizes": list(sizes)},
                lambda: fig8_fleet.run(n=n, fleet_sizes=sizes))
    if want("fig9"):
        from benchmarks import fig9_filtered

        if args.ci:
            n, nq = 4096, 16
        elif args.full:
            n, nq = 100_000, 64
        else:
            n, nq = 20_000, 64
        _figure("fig9", {"full": args.full, "ci": args.ci,
                         "n": n, "nq": nq},
                lambda: fig9_filtered.run(n=n, nq=nq))
    if want("roofline"):
        from benchmarks import roofline

        try:
            _figure("roofline", {"full": args.full}, roofline.run)
        except FileNotFoundError:
            print("roofline: no dryrun.json yet — run "
                  "python -m repro.launch.dryrun --all first",
                  file=sys.stderr)
    print(f"\nbenchmarks completed in {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
