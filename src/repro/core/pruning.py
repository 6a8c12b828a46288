"""Sprint-style record pruning (paper §3): device-resident row compaction.

When the fraction of rows sitting in CLOSED leaves reaches
`TreeParams.prune_closed_frac`, the drivers drop (a subset of) those rows
and filter every row-indexed array — the presorted order is FILTERED, not
re-sorted (stability preserves it), so the one-time cost is one pass, the
trade-off rule the paper describes.  Dropping any subset of closed rows
is result-invariant (closed rows never contribute to a split again), which
buys two generalizations over the seed implementation:

  * mesh engines: the drop count is rounded DOWN to the engine's row-shard
    width (`plan_drop`), so n stays shard_map-divisible;
  * the batched builder: only rows closed in EVERY tree of the batch are
    dropped (each is inside every tree's closed set, so each per-tree
    leaf-ordered prefix structure survives the filter).
"""
from __future__ import annotations

import jax.numpy as jnp


def plan_drop(n: int, closed: int, row_shards: int, frac: float) -> int:
    """How many closed rows to drop (0 = don't prune this level)."""
    if n <= 0 or closed <= 0 or closed / n < frac:
        return 0
    drop = closed - closed % row_shards
    return drop if 0 < drop < n else 0


def keep_mask(closed_mask: jnp.ndarray, drop: int) -> jnp.ndarray:
    """Keep everything except the first `drop` closed rows (row order)."""
    csum = jnp.cumsum(closed_mask.astype(jnp.int32))
    return (~closed_mask) | (csum > drop)


def compact_rows(*, keep, drop, leaf_of, ord_idx, sorted_vals, sorted_idx,
                 bin_of, num, cat, stats, w, labels, use_ord, hist, m_num):
    """Filter every row-indexed array down to the kept rows.

    Handles both driver layouts: per-tree (`leaf_of` (n,), `ord_idx`
    (m, n), `stats` (n, S)) and batched (`leaf_of` (T, n), `ord_idx`
    (T, m, n), `stats` (T, n, S)).  Under the leaf-ordered layout every
    dropped row sits in each tree's contiguous leaf-0 prefix, so filtering
    each (tree, column) order keeps it (leaf, value)-sorted; the
    permutation lands in ONE flat nonzero/gather over all T·m columns.
    Returns the updated (n, leaf_of, ord_idx, sorted_vals, sorted_idx,
    bin_of, num, cat, stats, w, labels).
    """
    batched = leaf_of.ndim == 2
    n = leaf_of.shape[-1]
    n_new = n - drop
    remap = jnp.cumsum(keep.astype(jnp.int32)) - 1
    keep_idx = jnp.nonzero(keep, size=n_new)[0]
    if use_ord:
        oi = ord_idx if batched else ord_idx[None]
        T = oi.shape[0]
        sel = jnp.take(keep, oi)                       # (T, m, n)
        flat = jnp.nonzero(sel.reshape(-1), size=T * m_num * n_new)[0]
        oi = jnp.take(remap, oi.reshape(-1)[flat]).reshape(T, m_num, n_new)
        ord_idx = oi if batched else oi[0]
    elif hist:
        # bucket ids are row-indexed; no sorted state to filter
        if m_num:
            bin_of = bin_of[:, keep_idx]
    elif m_num and sorted_vals.size:
        # filter the presorted order (stability preserves it): every column
        # keeps the same n_new rows, so the flat row-major nonzero is
        # (m_num, n_new) column blocks
        kept_cols = jnp.take(keep, sorted_idx)
        flat = jnp.nonzero(kept_cols.reshape(-1), size=m_num * n_new)[0]
        sorted_idx = jnp.take(remap,
                              sorted_idx.reshape(-1)[flat]).reshape(
            m_num, n_new)
        sorted_vals = sorted_vals.reshape(-1)[flat].reshape(m_num, n_new)
    num = num[:, keep_idx]                  # feature-major (m_num, n)
    cat = cat[keep_idx]
    labels = labels[keep_idx]
    if batched:
        stats = stats[:, keep_idx]
        w = w[:, keep_idx]
        leaf_of = leaf_of[:, keep_idx]
    else:
        stats = stats[keep_idx]
        w = w[keep_idx]
        leaf_of = leaf_of[keep_idx]
    return (n_new, leaf_of, ord_idx, sorted_vals, sorted_idx, bin_of, num,
            cat, stats, w, labels)
