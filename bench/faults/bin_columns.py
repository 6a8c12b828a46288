"""Reproduce, on the chip, the wrong bucket ids of `presort.bin_columns`
at n = 2^19 HIGGS rows, and show whether a fix holds.

    python3 bench/faults/bin_columns.py            # bucket ids only
    python3 bench/faults/bin_columns.py --cell 5   # and the higgs.hist
                                                   # cell at 2^19, 5 s

Chip only; no run of the benchmark calls it.  For each (configuration,
n, seed) it quantizes the generated rows as every hist `fit` does
(`presort.quantize`) and compares, column by column, the sorted values,
the edges and the bucket ids with numpy (`searchsorted` on the same
edges), and the bucket ids with the same `bin_columns` call on the CPU
backend of the same machine (the second witness).  On a TPU v5e the
bucket ids of the last 128 rows of the last column were wrong at 2^19
(seeds 3000000201 and 5) and right at 2^20 and at Covertype's 145,253.
With `--cell S` it then runs the `higgs.hist` cell at 2^19 rows for an
S-second window and prints its checks: `correct` false while the fault
stands.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "configs"), str(BENCH.parent / "src")]

CASES = (("higgs", 1 << 19, 3000000201), ("higgs", 1 << 19, 5),
         ("higgs", 1 << 20, 1000001), ("covertype", 145253, 3000000301))


def check_buckets(gen, n, seed, num_bins=255) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import presort
    X, _ = gen.generate(n, seed)
    num = jnp.asarray(X)
    sv = presort.gather_sorted(num, presort.presort_columns(num))
    bins, edges = presort.quantize(num, sv, num_bins)
    sv, bins, edges = np.asarray(sv), np.asarray(bins), np.asarray(edges)
    want_bins = np.stack([np.searchsorted(edges[j, :-1], X[:, j])
                          for j in range(X.shape[1])])
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        cpu_bins = np.asarray(presort.bin_columns(
            jax.device_put(X, cpu), jax.device_put(edges, cpu)))
    bad = bins != want_bins
    rows = {int(j): np.flatnonzero(bad[j]).tolist()[:4]
            for j in np.flatnonzero(bad.any(1))}
    return {"n": n, "seed": seed,
            "sorted_wrong": int((sv != np.sort(X, axis=0).T).sum()),
            "bins_wrong": int(bad.sum()), "first_wrong_rows": rows,
            "cpu_bins_wrong": int((cpu_bins != want_bins).sum())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cell", type=float, default=0,
                    help="seconds of a higgs.hist window at 2^19 rows")
    ap.add_argument("--seed", type=int, default=3000000201)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH.parent / ".jax_cache")
    import jax

    import harness
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    for config, n, seed in CASES:
        gen = harness.load_module(BENCH / "configs" / f"{config}.py", config)
        print(json.dumps({"config": config,
                          **check_buckets(gen, n, seed)}), flush=True)
    if args.cell:
        import run
        cell = harness.load_cell("higgs.hist")
        cell["traffic"]["rows"] = 1 << 19
        line = run.run_cell("higgs.hist", args.seed, args.cell, 0,
                            devices=jax.devices()[:1], cell=cell)
        print(json.dumps({"cell": "higgs.hist", "rows": 1 << 19,
                          "correct": line["correct"],
                          "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
