"""Benchmark driver: one bench per paper table/figure (DESIGN.md §6).

Prints ``name,us_per_call,derived`` CSV rows. The dry-run/roofline tables
(assignment §Dry-run/§Roofline) live in dryrun_results.json, produced by
``python -m repro.launch.dryrun``; ``bench_roofline`` summarises them here.

``--smoke`` runs the mining-perf ladder plus the fused-superstep,
checkpoint-overhead, aggregation-bytes, graph-shard, observability,
and fault-recovery gates — the quick sanity sweep behind
``make bench-smoke``.
``--json [PATH]`` additionally writes every emitted row (us_per_call +
parsed derived stats) as machine-readable JSON — the default path is
``benchmarks.common.DEFAULT_BENCH_JSON`` (``BENCH_<version>.json``, one
constant shared with the Makefile and CI) — the perf trajectory future
PRs gate against instead of an empty history.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import traceback

from benchmarks.common import DEFAULT_BENCH_JSON


def main(argv=None) -> None:
    args = argparse.ArgumentParser(description=__doc__)
    args.add_argument(
        "--smoke", action="store_true",
        help="run only the fast mining-perf ladder + superstep gate",
    )
    args.add_argument(
        "--json", nargs="?", const=DEFAULT_BENCH_JSON, default=None,
        metavar="PATH",
        help=f"write emitted rows as JSON (default: {DEFAULT_BENCH_JSON})",
    )
    opts = args.parse_args(argv)
    from repro.compile_cache import use_compile_cache

    use_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))))
    from benchmarks import (
        bench_aggregate,
        bench_breakdown,
        bench_canon,
        bench_checkpoint,
        bench_faults,
        bench_graphshard,
        bench_large,
        bench_mining_perf,
        bench_obs,
        bench_odag,
        bench_paradigms,
        bench_roofline,
        bench_single_thread,
        bench_scalability,
        bench_superstep,
        bench_two_level,
    )

    benches = [
        ("paradigms(fig7)", bench_paradigms.main),
        ("single_thread(table2)", bench_single_thread.main),
        ("scalability(table3/fig8)", bench_scalability.main),
        ("odag(fig9/10)", bench_odag.main),
        ("two_level(table4/fig11)", bench_two_level.main),
        ("breakdown(fig12)", bench_breakdown.main),
        ("large(table5)", bench_large.main),
        ("mining_perf(§Perf)", bench_mining_perf.main),
        ("superstep(§8)", bench_superstep.main),
        ("checkpoint(§9)", bench_checkpoint.main),
        ("aggregate(§10)", bench_aggregate.main),
        ("graphshard(§11)", bench_graphshard.main),
        ("obs(§12)", bench_obs.main),
        ("faults(§13)", bench_faults.main),
        ("canon(§15)", bench_canon.main),
        ("roofline(dry-run)", bench_roofline.main),
    ]
    if opts.smoke:
        benches = [
            ("mining_perf(§Perf)", bench_mining_perf.main),
            ("superstep(§8)", bench_superstep.main),
            ("checkpoint(§9)", bench_checkpoint.main),
            ("aggregate(§10)", bench_aggregate.main),
            ("graphshard(§11)", bench_graphshard.main),
            ("obs(§12)", bench_obs.main),
            ("faults(§13)", bench_faults.main),
        ]
    failures = 0
    for name, fn in benches:
        print(f"# --- {name} ---", flush=True)
        try:
            fn()
        except Exception:
            failures += 1
            traceback.print_exc()
    if opts.json:
        import jax

        from benchmarks.common import RECORDS, warn_missing_previous

        warn_missing_previous()

        with open(opts.json, "w") as f:
            json.dump(
                {
                    "benches": RECORDS,
                    "failures": failures,
                    "backend": jax.default_backend(),
                    "python": platform.python_version(),
                },
                f,
                indent=2,
            )
        print(f"# wrote {len(RECORDS)} rows to {opts.json}", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
