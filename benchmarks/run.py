"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (repo convention).
Roofline terms come from the dry-run (launch/dryrun.py) — see
roofline_report.py and EXPERIMENTS.md §Roofline.

Usage:
  python -m benchmarks.run                  # every benchmark, full scale
  python -m benchmarks.run all --smoke      # every benchmark, seconds-scale
  python -m benchmarks.run forest --smoke   # one benchmark
  python -m benchmarks.run dist             # sharded batched-vs-per-tree

Perf-regression gate: ``python -m benchmarks.check_regression`` re-runs
the smoke benchmarks and fails on >2× slowdown vs the committed
``BENCH_smoke_baseline.json`` (wired into ``pytest -m slow``).
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    from repro import compile_cache
    compile_cache.configure()
    from benchmarks import (dist_batch_bench, fig1_auc_scaling,
                            fig2_time_scaling, fig3_depth_metrics,
                            forest_batch_bench, hist_mode_bench,
                            kernel_bench, level_step_bench,
                            outofcore_bench, serve_bench,
                            table1_complexity)
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    flags = {a for a in sys.argv[1:] if a.startswith("--")}
    unknown = flags - {"--smoke", "--full", "--checkpoint"}
    if unknown:
        raise SystemExit(f"unknown flags: {sorted(unknown)} "
                         "(supported: --smoke, --full, --checkpoint)")
    only = args[0] if args else None
    if only == "all":           # explicit umbrella (same as no selector)
        only = None
    smoke = "--smoke" in flags
    full = "--full" in flags
    benches = {
        "table1": table1_complexity.run,
        "fig2": fig2_time_scaling.run,
        "fig3": fig3_depth_metrics.run,
        "kernel": kernel_bench.run,
        "fig1": fig1_auc_scaling.run,
        # writes BENCH_level_step.json (fused vs reference per-level time)
        "level": level_step_bench.run,
        # writes BENCH_forest_batch.json (batched vs per-tree forest fit);
        # honours --smoke (seconds-scale) and --full (adds the 250k point)
        "forest": lambda: forest_batch_bench.run(full=full, smoke=smoke),
        # writes BENCH_hist_mode.json (exact vs PLANET-style histogram
        # mode: AUC delta + fit-wall matrix); honours --smoke
        "hist": lambda: hist_mode_bench.run(smoke=smoke),
        # writes BENCH_dist_batch.json (sharded training: batched vs
        # per-tree level programs on the 2x4 host mesh); honours --smoke
        "dist": lambda: dist_batch_bench.run(smoke=smoke),
        # writes BENCH_serve.json (ForestServer.load + p50 single-row
        # predict latency off the warm packed-forest descent)
        "serve": lambda: serve_bench.run(smoke=smoke),
        # writes BENCH_outofcore.json (streamed fit from a disk-backed
        # bin cache: rows/sec vs n, target n >= 20M); honours --smoke;
        # --checkpoint adds a checkpointed fit per point and records the
        # checkpoint-write overhead fraction (smoke always measures it)
        "outofcore": lambda: outofcore_bench.run(smoke=smoke,
                                                 checkpoint="--checkpoint"
                                                 in flags),
    }
    if only and only not in benches:
        raise SystemExit(f"unknown benchmark {only!r} "
                         f"(have: {', '.join(benches)}, or 'all')")
    print("name,us_per_call,derived")
    for name, fn in benches.items():
        if only and name != only:
            continue
        t0 = time.time()
        fn()
        print(f"bench/{name}/wall,{(time.time() - t0) * 1e6:.0f},", flush=True)


if __name__ == "__main__":
    main()
