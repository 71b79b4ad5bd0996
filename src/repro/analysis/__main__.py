"""CLI: ``python -m repro.analysis [paths...] [--strict]``.

Exit code 0 when no unsuppressed finding remains, 1 otherwise — the CI
``lint`` job runs ``python -m repro.analysis src/repro --strict`` as a
blocking gate.  Without ``--strict`` only the pure-AST passes run (no
jax import, sub-second); ``--strict`` adds the dynamic recompile gate,
which builds real backends on a 1-device mesh.
"""
from __future__ import annotations

import argparse
import os
import sys


def main(argv=None) -> int:
    # the gate drives 1-device CPU meshes; select the CPU backend before
    # any jax import so it does not start on (or look for) a TPU
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="compile-path & concurrency lint for the repro stack")
    ap.add_argument("paths", nargs="*", default=["src/repro"],
                    help="files/directories to analyze (default: src/repro)")
    ap.add_argument("--strict", action="store_true",
                    help="also run the dynamic recompile-stability gate "
                         "(imports jax, drives real backends)")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule ids to report "
                         "(suppression-hygiene rules always run)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    args = ap.parse_args(argv)

    # importing the checkers populates the full rule catalog
    from repro.analysis import core
    from repro.analysis import jaxlint, locks  # noqa: F401

    if args.list_rules:
        width = max(len(r) for r in core.STATIC_RULES)
        for rid in sorted(core.STATIC_RULES):
            print(f"{rid:<{width}}  {core.STATIC_RULES[rid]}")
        return 0

    extra = []
    if args.strict:
        from repro.analysis.recompile import run_recompile_gate

        extra = run_recompile_gate()

    rules = (set(r.strip() for r in args.rules.split(",") if r.strip())
             if args.rules else None)
    active, suppressed = core.run_static_analysis(
        args.paths, rules=rules, extra_findings=extra)
    for f in active:
        print(f.format())
    print(f"{len(active)} finding(s), {len(suppressed)} suppressed"
          + (" [strict]" if args.strict else ""),
          file=sys.stderr)
    return 1 if active else 0


if __name__ == "__main__":
    raise SystemExit(main())
