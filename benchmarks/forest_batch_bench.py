"""Multi-tree batched fit vs the per-tree builder (DESIGN.md §3).

Times `RandomForest.fit` with the whole forest in one tree batch (one
jitted level program per depth for ALL trees) against the per-tree builder
(`tree_batch=1`, one program per depth PER TREE), verifies the two produce
bit-identical forests, and writes the matrix to ``BENCH_forest_batch.json``
so the perf trajectory stays machine-readable across PRs.

Two workload points: the fig2-scale n=100k headline (where the level
programs are compute-bound and the win comes from removing the per-tree
host round trips — lax.map lowering) and a small-n point (where dispatch
overhead dominates and the vmap lowering's cross-tree SIMD pays most —
the regime arXiv:1910.06853 targets).  The speedup is hardware-dependent:
per-tree dispatch overhead that batching amortizes is a far larger share
of the level time on accelerators than on a small CPU.

Smoke mode (`--smoke` / run(smoke=True)) shrinks both points so the tier-1
suite can run the whole benchmark in seconds.
"""
from __future__ import annotations

import json
import os
import time

from benchmarks.common import emit

OUT_PATH = os.environ.get("BENCH_FOREST_BATCH_JSON", "BENCH_forest_batch.json")


def _fit_seconds(ds, params, n_trees, tree_batch, seed):
    """One warm fit (compile) + best-of-2 timed fits; returns (s, forest,
    level-program dispatches per timed fit)."""
    from repro import obs
    from repro.core import tree as tree_lib
    from repro.core.forest import RandomForest

    # warm with the SAME seed that is timed, so no jit compile (new padded
    # leaf counts / depth schedules) can leak into the timed region
    RandomForest(params, num_trees=n_trees, seed=seed,
                 tree_batch=tree_batch).fit(ds)              # warm jits
    best, forest, programs = float("inf"), None, 0
    for rep in (1, 2):
        c0 = (obs.counter("level.tree_dispatches"),
              obs.counter("level.dispatches"))
        t0 = time.perf_counter()
        rf = RandomForest(params, num_trees=n_trees, seed=seed,
                          tree_batch=tree_batch).fit(ds)
        dt = time.perf_counter() - t0
        if rep == 1:
            forest = rf          # for the cross-path parity check
            programs = (obs.counter("level.tree_dispatches") - c0[0]
                        + obs.counter("level.dispatches") - c0[1])
        best = min(best, dt)
    return best, forest, programs


def _bench_point(n, n_trees, depth):
    import numpy as np
    from repro.core import tree as tree_lib
    from repro.data.synthetic import make_tabular

    ds = make_tabular("majority", n, num_informative=4, num_useless=4,
                      seed=7)
    params = tree_lib.TreeParams(max_depth=depth, min_records=1)

    per_s, per_rf, per_prog = _fit_seconds(ds, params, n_trees, 1, 10)
    bat_s, bat_rf, bat_prog = _fit_seconds(ds, params, n_trees, n_trees, 10)

    # the two fits must be the same forest, bit for bit
    for ta, tb in zip(per_rf.trees, bat_rf.trees):
        np.testing.assert_array_equal(ta.feature, tb.feature)
        np.testing.assert_array_equal(ta.threshold, tb.threshold)
        np.testing.assert_array_equal(ta.value, tb.value)

    speedup = per_s / bat_s if bat_s else float("nan")
    emit(f"forest_batch/per_tree/n{n}", per_s / n_trees * 1e6,
         f"s_total={per_s:.3f};programs={per_prog}")
    emit(f"forest_batch/batched/n{n}", bat_s / n_trees * 1e6,
         f"s_total={bat_s:.3f};programs={bat_prog}")
    emit(f"forest_batch/speedup/n{n}", 0.0, f"x{speedup:.2f}")
    return {
        "n": n, "n_trees": n_trees, "max_depth": depth,
        "per_tree_s": round(per_s, 4), "batched_s": round(bat_s, 4),
        "speedup": round(speedup, 3),
        "level_programs_per_tree": per_prog,
        "level_programs_batched": bat_prog,
    }


def run(full: bool = False, smoke: bool = False):
    import jax

    if smoke:
        points = [(4_000, 8, 5)]
    else:
        # headline: the fig2 workload; secondary: the small-n regime
        points = [(100_000, 16, 8), (4_000, 16, 8)]
        if full:
            points.append((250_000, 16, 8))

    results = [_bench_point(n, t, d) for n, t, d in points]
    report = {
        "workload": {"family": "majority", "m_num": 8, "backend": "segment",
                     "device": jax.default_backend(),
                     "cpu_count": os.cpu_count()},
        "points": results,
        "speedup": results[0]["speedup"],        # headline point
        "smoke": smoke,
        "note": ("speedup = per-tree fit wall / batched fit wall for an "
                 "identical (bit-exact) forest; batched issues one level "
                 "program per depth for ALL trees, per-tree issues one per "
                 "depth per tree — the amortized dispatch/host-sync share "
                 "is hardware-dependent (largest on accelerators)"),
    }
    with open(OUT_PATH, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    emit("forest_batch/json", 0.0, OUT_PATH)
    return report


def main() -> None:
    import sys
    run(full="--full" in sys.argv, smoke="--smoke" in sys.argv)


if __name__ == "__main__":
    main()
