"""The `serve` traffic kind: open-loop requests to a `ForestServer`.

Set-up generates a forest of complete trees from the seed (split
columns uniform, thresholds at the columns' quantiles, leaf
distributions uniform), saves it with `PackedForest.save`, loads it with
`ForestServer.load(warm_batch_sizes=...)` (every request size of the
mix), and draws a pool of the configuration's rows to send.

The schedule is fixed by the traffic file and permuted by the seed: the
window holds round(rate · seconds) requests; their sizes follow the mix
exactly and their gaps are the exponential distribution's quantiles at
(k + ½)/N (Poisson arrivals), so every seed sends the same sizes and
gaps in another order.  One client thread sends each request when it is
due, or at once when it is late, and waits for the answer on the host;
a request's latency runs from when it was due to when its answer is on
the host, so a stall counts against every request queued behind it.

End to end: `serve_p95_ms` over every request of the window.  Correct:
a sample of the answers, drawn from the seed, each compared with
`reference.forest_proba` of the generated forest.

Traffic keys: num_trees, depth, rate (requests/s), sizes {rows: share},
rows (rows in the pool), check_requests, trace_seconds (how much of the
window a `--trace 1` run traces) and limits {proba_err, failed}.
"""
from __future__ import annotations

import math
import os
import sys
import tempfile

import numpy as np

import harness
import reference


def make_forest(seed: int, pool: np.ndarray, num_trees: int, depth: int,
                num_classes: int = 2) -> dict:
    """Complete trees, children of node i at 2i+1 and 2i+2."""
    rng = np.random.default_rng([seed, 2])
    n, m = pool.shape
    inner, N = 2 ** depth - 1, 2 ** (depth + 1) - 1
    srt = np.sort(pool, axis=0)
    feature = np.full((num_trees, N), -1, np.int32)
    feature[:, :inner] = rng.integers(0, m, (num_trees, inner))
    q = rng.integers(n // 20, n - n // 20, (num_trees, inner))
    threshold = np.zeros((num_trees, N), np.float32)
    threshold[:, :inner] = srt[q, feature[:, :inner]]
    children = np.full((num_trees, N, 2), -1, np.int32)
    ids = np.arange(inner)
    children[:, :inner, 0] = 2 * ids + 1
    children[:, :inner, 1] = 2 * ids + 2
    value = np.zeros((num_trees, N, num_classes), np.float32)
    p = rng.dirichlet(np.ones(num_classes), (num_trees, N - inner))
    value[:, inner:] = p.astype(np.float32)
    return {"feature": feature, "threshold": threshold, "children": children,
            "value": value, "depth": depth}


def schedule(seed: int, traffic: dict, seconds: float):
    """(arrival offsets (N,), request sizes (N,)) of one window."""
    rate = float(traffic["rate"])
    N = max(1, int(round(rate * seconds)))
    sizes = sorted(traffic["sizes"].items(), key=lambda kv: -kv[1])
    counts = [int(math.floor(share * N)) for _, share in sizes]
    counts[0] += N - sum(counts)
    rows = np.repeat([int(k) for k, _ in sizes], counts)
    rows = np.random.default_rng([seed, 3]).permutation(rows)
    gaps = -np.log1p(-(np.arange(N) + 0.5) / N) / rate
    gaps = np.random.default_rng([seed, 4]).permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]]), rows


def _wait_until(t):
    while True:
        d = t - harness.now()
        if d <= 0:
            return
        if d > 2e-3:
            import time
            time.sleep(d - 1e-3)


def run(ctx) -> dict:
    import jax.numpy as jnp

    from repro.core.forest import PackedForest
    from repro.serve.engine import ForestServer
    cfg, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    pool, _ = ctx["generate"](traffic["rows"], seed)
    forest = make_forest(seed, pool, traffic["num_trees"], traffic["depth"],
                         cfg["classes"])
    T, N = forest["feature"].shape
    packed = PackedForest(
        feature=jnp.asarray(forest["feature"]),
        threshold=jnp.asarray(forest["threshold"]),
        is_cat=jnp.zeros((T, N), bool), cat_mask=jnp.zeros((T, N, 1), bool),
        children=jnp.asarray(forest["children"]),
        value=jnp.asarray(forest["value"]), m_num=pool.shape[1],
        iters=forest["depth"] + 1)
    sizes = tuple(sorted(int(k) for k in traffic["sizes"]))
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "forest.npz")
        packed.save(path)
        del packed
        srv = ForestServer.load(path, warm_batch_sizes=sizes)

    arrival, rows = schedule(seed, traffic, ctx["seconds"])
    start = np.random.default_rng([seed, 5]).integers(
        0, len(pool) - rows + 1)
    reqs = [pool[s:s + r] for s, r in zip(start, rows)]
    check = set(np.random.default_rng([seed, 6]).choice(
        len(reqs), size=min(traffic["check_requests"], len(reqs)),
        replace=False).tolist())
    check.update(np.flatnonzero(rows == rows.max())[:8].tolist())

    for r in sizes:                     # every request shape, end to end
        for _ in range(20):
            np.asarray(srv.predict(pool[:r]))

    compiles, prof = ctx["compiles"], ctx["profile"]
    traced = arrival < traffic["trace_seconds"]
    c0 = compiles.mark()
    ctx["window_start"]()
    prof.start()
    lat = np.full(len(reqs), np.nan)
    answers, failed = {}, 0
    t0 = harness.now() + 1e-3
    for i, x in enumerate(reqs):
        due = t0 + arrival[i]
        if harness.now() < due:
            with harness.span("bench.wait"):
                _wait_until(due)
        try:
            with harness.span("bench.request"):
                out = np.asarray(srv.predict(x))
        except Exception as e:          # a failed request misses its limit
            failed += 1
            print(f"request {i} failed: {e!r}", file=sys.stderr)
            continue
        lat[i] = harness.now() - due
        if i in check:
            answers[i] = out
        if prof.enabled and traced[i] and (i + 1 == len(reqs)
                                           or not traced[i + 1]):
            prof.stop(ctx["devices"])
    t1 = harness.now()
    prof.stop(ctx["devices"])
    bad = np.isnan(lat)                     # failed: answered at the close
    lat[bad] = t1 - (t0 + arrival[bad])
    c1 = compiles.mark()
    device = harness.device_info(ctx["devices"], prof.summary)
    del srv

    err = 0.0
    if answers:
        keys = sorted(answers)
        ref = reference.forest_proba(forest, np.concatenate(
            [reqs[i] for i in keys]))
        at = np.cumsum([0] + [len(reqs[i]) for i in keys])
        for k, i in enumerate(keys):
            want = ref[at[k]:at[k + 1]]
            if answers[i].shape != want.shape:
                failed += 1
                continue
            err = max(err, float(np.abs(answers[i].astype(np.float64)
                                        - want).max()))
    limits = traffic["limits"]
    out = {
        "attempted": len(reqs), "failed": failed, "device": device,
        "end_to_end": {"serve_p95_ms": float(np.percentile(lat, 95)) * 1e3},
        "checks": {"proba_err": (err, limits["proba_err"]),
                   "failed": (failed, limits["failed"])},
        "window_compiles": c1[1] - c0[1],
        "info": {"requests": len(reqs), "window_s": t1 - t0,
                 "p50_ms": float(np.percentile(lat, 50)) * 1e3,
                 "first_quarter_p50_ms": float(np.median(
                     lat[:max(1, len(lat) // 4)])) * 1e3,
                 "last_quarter_p50_ms": float(np.median(
                     lat[-max(1, len(lat) // 4):])) * 1e3,
                 "lag_s": float(t1 - (t0 + arrival[-1]))},
    }
    return out
