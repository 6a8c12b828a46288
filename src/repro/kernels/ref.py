"""Pure-jnp oracles for the Pallas kernels.

`split_scan_ref` is the faithful Alg. 1 sequential scan
(`repro.core.splits.best_numeric_split_scan`) vmapped over columns — the
semantics the TPU kernel must reproduce.  `cat_hist_ref` is a plain
segment-sum count table.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import splits


@functools.partial(jax.jit, static_argnames=("L1", "s_dim", "impurity", "task",
                                             "min_records"))
def split_scan_ref(vals, leaf, w, y, cand, totals, *, L1, s_dim,
                   impurity="gini", task="classification", min_records=1.0):
    """Same contract as kernels.split_scan.split_scan_pallas.

    vals/leaf/w/y: (m, n) in per-column presorted order; cand: (m, L1)
    float mask; totals: (m, L1, S).  Returns (gain (m, L1), thr (m, L1)).
    """
    def per_col(v, lf, ww, yy, cl, tot):
        stats = splits.row_stats(yy, ww, s_dim, task)
        return splits.best_numeric_split_scan(
            v, lf, ww, stats, cl > 0, L1 - 1, impurity, task, min_records,
            totals=tot)

    return jax.vmap(per_col)(vals, leaf, w, y, cand, totals)


@functools.partial(jax.jit, static_argnames=("L1", "V", "s_dim", "task"))
def cat_hist_ref(x, leaf, w, y, *, L1, V, s_dim, task="classification"):
    """Count table (m, L1, S, V) — one pass per column."""
    def col(xc, lf, ww, yy):
        stats = splits.row_stats(yy, ww, s_dim, task)
        inbag = (ww > 0) & (lf > 0)
        contrib = jnp.where(inbag[:, None], stats, 0.0)
        flat = lf * V + xc
        return jax.ops.segment_sum(contrib, flat, num_segments=L1 * V).reshape(
            L1, V, s_dim).transpose(0, 2, 1)

    return jax.vmap(col)(x, leaf, w, y)
