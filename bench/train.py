"""The `train` traffic kind: forests fitted back to back on one dataset.

Set-up generates the configuration's rows from the seed, puts them on
the device once (as a user training several forests on one dataset
would) and warms every program with one whole fit.  The window then runs
`RandomForest(params, num_trees=T, seed=forest_seed(seed, i)).fit(ds)`
back to back, each fit with its presort and quantize, from the first fit
until the end of the fit that is running when `seconds` have passed.

End to end: `tree_rows_per_s`, the T·n of every fit of the window over
the window's length.  Correct: `check_trees` of the window's trees,
walked over the data by `reference.check_tree`: consecutive tree indices
(mod T) from a start drawn from the seed, each from a fit drawn from the
seed, so that every position of a tree batch of at most `check_trees`
trees is checked in every run (see `check_picks`).

Traffic keys: tree_params (`TreeParams` fields; the library's defaults
for the rest, the tree batch too), num_trees, rows (the rows the job
trains on; null = the configuration's), check_trees and limits
{gain_gap, node_errors}.
"""
from __future__ import annotations

import dataclasses
import sys

import numpy as np

import harness
import reference
import roofline


def _params(traffic):
    from repro.core.tree import TreeParams
    return TreeParams(**traffic["tree_params"])


def _fit(ds, params, traffic, seed):
    import jax

    from repro.core.forest import RandomForest
    rf = RandomForest(params=params, num_trees=traffic["num_trees"],
                      seed=seed)
    rf.fit(ds)
    jax.block_until_ready(rf.packed.value)
    return rf.trees


def check_picks(seed: int, num_fits: int, num_trees: int,
                k: int) -> list:
    """(fit, tree) pairs to check: k consecutive tree indices mod T from a
    start drawn from the seed, each in a fit drawn from the seed.

    The library batches trees 0..b-1, b..2b-1, ...; where b divides T,
    k consecutive indices hold every position of a batch once k >= b, so
    a fault in one position of the batched program is never missed."""
    rng = np.random.default_rng([seed, 1])
    start = int(rng.integers(num_trees))
    trees = [(start + j) % num_trees for j in range(min(k, num_trees))]
    return sorted((int(rng.integers(num_fits)), t) for t in trees)


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core.dataset import from_numpy
    cfg, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    n = traffic.get("rows") or cfg["rows"]
    X, y = ctx["generate"](n, seed)
    ds = from_numpy(X, None, y)
    ds = dataclasses.replace(ds, num=jnp.asarray(ds.num),
                             cat=jnp.asarray(ds.cat),
                             labels=jnp.asarray(ds.labels))
    params = _params(traffic)
    T = traffic["num_trees"]
    _fit(ds, params, traffic, harness.forest_seed(seed, 0))

    compiles, prof = ctx["compiles"], ctx["profile"]
    c0 = compiles.mark()
    ctx["window_start"]()
    prof.start()
    host = harness.HostLoad()
    fits, per_fit, t0 = [], [], harness.now()
    while True:
        fs = harness.forest_seed(seed, len(fits) + 1)
        h0 = host.mark()
        with harness.span("bench.fit"):
            fits.append((fs, _fit(ds, params, traffic, fs)))
        per_fit.append(host.since(h0))
        if harness.now() - t0 >= ctx["seconds"]:
            break
    t1 = harness.now()
    host.close()
    prof.stop(ctx["devices"])
    c1 = compiles.mark()
    device = harness.device_info(ctx["devices"], prof.summary)
    del ds

    rate = len(fits) * T * n / (t1 - t0)
    data = reference.Data(X, y, cfg["classes"], params.split_mode,
                          params.num_bins)
    gap, errors = 0.0, 0
    for i, t in check_picks(seed, len(fits), T, traffic["check_trees"]):
        fs, trees = fits[i]
        res = reference.check_tree(trees[t], data, fs, t,
                                   max_depth=params.max_depth,
                                   min_records=params.min_records)
        gap, errors = max(gap, res["gain_gap"]), errors + res["node_errors"]
        print(f"checked fit {i} (forest seed {fs}) tree {t}: {res}",
              file=sys.stderr)
    errors += sum(len(trees) != T for _, trees in fits)
    limits = traffic["limits"]
    out = {
        "attempted": len(fits), "failed": 0, "device": device,
        "end_to_end": {"tree_rows_per_s": rate},
        "checks": {"gain_gap": (gap, limits["gain_gap"]),
                   "node_errors": (errors, limits["node_errors"])},
        "window_compiles": c1[1] - c0[1],
        "info": {"fits": len(fits), "rows": n, "window_s": t1 - t0,
                 "per_fit": {k: [f[k] for f in per_fit]
                             for k in per_fit[0]}},
    }
    if prof.summary is not None:
        levels = [roofline.tree_levels(tr, X, fs, t, params.max_depth,
                                       params.min_records)
                  for fs, trees in fits for t, tr in enumerate(trees)]
        out["layer_inputs"] = {
            "level_bytes": sum(roofline.level_bytes(
                lv, m=X.shape[1], mode=params.split_mode,
                num_bins=params.num_bins, num_classes=cfg["classes"])
                for lv in levels)}
    return out
