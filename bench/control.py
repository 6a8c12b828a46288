"""The controls that the comparisons deciding `correct` must reject.

Training: the reference builder put in the program's place with its
gains rounded to bfloat16, the precision below the float32 the library
computes in (`reference.build_tree(dtype=bfloat16)`), read by the same
`reference.check_tree`.  Serving: the forest descent with leaf values
and the running sum in bfloat16, read against `reference.forest_proba`.

    python bench/control.py --workload <cell> --seeds 3 [--trees k]

runs the control at the cell's own size on this machine (numpy on the
host; the seeded draws through `jax.random`) and prints one line per
seed with the numbers the cell compares.  The benchmark's own runs do
not run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import reference  # noqa: E402


def bf16():
    import ml_dtypes
    return ml_dtypes.bfloat16


def train_control(cell, seed, trees):
    """gain_gap and node_errors of bfloat16-gain trees."""
    traffic, cfg = cell["traffic"], cell["config"]
    tp = traffic["tree_params"]
    n = traffic.get("rows") or cfg["rows"]
    X, cat, y, arities = harness.rows(cell, n, seed)
    data = reference.Data(X, y, cfg["classes"], tp["split_mode"],
                          tp.get("num_bins", 255), cat=cat, arities=arities)
    gap, errors = 0.0, 0
    for t in range(trees):
        fs = harness.forest_seed(seed, 1)
        tree = reference.build_tree(data, fs, t, max_depth=tp["max_depth"],
                                    dtype=bf16())
        res = reference.check_tree(tree, data, fs, t,
                                   max_depth=tp["max_depth"])
        gap, errors = max(gap, res["gain_gap"]), errors + res["node_errors"]
    return {"gain_gap": gap, "node_errors": errors}


def forest_proba_bf16(forest, X):
    """`reference.forest_proba` with leaf values and the sum in bfloat16."""
    dt = bf16()
    low = dict(forest, value=forest["value"].astype(dt))
    out = np.zeros((len(X), forest["value"].shape[2]), dt)
    for t in range(forest["feature"].shape[0]):
        one = dict(low, feature=low["feature"][t:t + 1],
                   threshold=low["threshold"][t:t + 1],
                   children=low["children"][t:t + 1],
                   value=low["value"][t:t + 1].astype(np.float64))
        out = (out + reference.forest_proba(one, X).astype(dt)).astype(dt)
    return out.astype(np.float64) / forest["feature"].shape[0]


def serve_control(cell, seed, requests):
    """proba_err of the bfloat16 descent over a sample of requests."""
    import serve
    traffic = cell["traffic"]
    pool, _ = cell["generate"](traffic["rows"], seed)
    forest = serve.make_forest(seed, pool, traffic["num_trees"],
                               traffic["depth"], cell["config"]["classes"])
    _, rows = serve.schedule(seed, traffic, 45.0)
    rng = np.random.default_rng([seed, 6])
    err = 0.0
    for r in rng.choice(rows, size=min(requests, len(rows)), replace=False):
        s = int(rng.integers(0, len(pool) - r + 1))
        x = pool[s:s + r]
        err = max(err, float(np.abs(forest_proba_bf16(forest, x)
                                    - reference.forest_proba(forest, x)
                                    ).max()))
    return {"proba_err": err}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1)
    ap.add_argument("--trees", type=int, default=1)
    ap.add_argument("--requests", type=int, default=200)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        if cell["traffic"]["kind"] == "train":
            res = train_control(cell, seed, args.trees)
        else:
            res = serve_control(cell, seed, args.requests)
        print(json.dumps({"workload": args.workload, "seed": seed, **res}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
