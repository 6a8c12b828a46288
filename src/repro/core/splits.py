"""Supersplit search (paper §2.4, Alg. 1).

A *supersplit* is the set of best splits for every open leaf at the current
depth, computed in ONE pass per candidate feature over the presorted data.

Unified statistics
------------------
Split scoring works on per-leaf "stats" accumulators so the same engines
serve Random Forests (classification) and Gradient Boosted Trees
(regression, paper §1 "can be applied to other DF models, notably GBT"):

  * classification: stats[k] = bag_weight * one_hot(label, C)        (S = C)
  * regression:     stats[k] = bag_weight * [1, y, y^2]              (S = 3)

`weighted_impurity(H)` returns N·impurity so that
gain = imp(parent) − imp(left) − imp(right) is additive.

Two exact numerical backends (identical results, different machines):

  * `scan`    — the faithful Alg. 1: a sequential pass carrying one histogram
                per open leaf (H ∈ (ℓ+1, S)) plus the last-seen value v_h.
                This is the reference semantics and the shape the Pallas
                kernel (`repro.kernels.split_scan`) implements on TPU.
  * `segment` — beyond-paper TPU-native backend: a stable counting-sort of
                the presorted order by leaf id makes every leaf contiguous;
                per-leaf cumulative histograms then become segmented cumsums
                — fully parallel across rows (no sequential carry), which is
                what the VPU wants.  Bitwise-equal split choices up to
                floating-point summation order.

Leaf id convention: 0 = closed (sentinel, paper §2.3), open leaves 1..ℓ.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

NEG = jnp.float32(-jnp.inf)

# ---------------------------------------------------------------------------
# Stats & impurities
# ---------------------------------------------------------------------------

def row_stats(labels: jnp.ndarray, weights: jnp.ndarray, num_classes: int,
              task: str) -> jnp.ndarray:
    """Per-row stats contributions, (n, S)."""
    if task == "classification":
        return jax.nn.one_hot(labels, num_classes, dtype=jnp.float32) * weights[:, None]
    y = labels.astype(jnp.float32)
    return jnp.stack([weights, weights * y, weights * y * y], axis=-1)


def stat_planes(labels: jnp.ndarray, weights: jnp.ndarray, num_classes: int,
                task: str) -> jnp.ndarray:
    """`row_stats` with the stat axis FIRST, (S, *labels.shape), built
    elementwise so that no (..., S) array is ever materialized."""
    if task == "classification":
        return jnp.stack([jnp.where(labels == s, weights, 0.0)
                          for s in range(num_classes)])
    y = labels.astype(jnp.float32)
    return jnp.stack([weights, weights * y, weights * y * y])


# Row- and table-sized arrays keep the stat axis S (2-3 wide) OFF the minor
# axis: a TPU lays the minor axis out in 128-lane tiles, so an (n, S) or
# (..., B, S) array occupies 128/S times its size in HBM.  The helpers
# below therefore take the stat axis as an argument.

def count_fn(task: str, axis: int = -1) -> Callable[[jnp.ndarray], jnp.ndarray]:
    if task == "classification":
        return lambda h: h.sum(axis)
    return lambda h: jnp.take(h, 0, axis=axis)


def weighted_impurity(h: jnp.ndarray, impurity: str,
                      axis: int = -1) -> jnp.ndarray:
    """N * impurity for a stats accumulator h with its S stats on `axis`.
    Safe at N=0."""
    if impurity == "gini":
        n = h.sum(axis)
        return n - jnp.where(n > 0, (h * h).sum(axis) / jnp.maximum(n, 1e-12), 0.0)
    if impurity == "entropy":
        n = h.sum(axis, keepdims=True)
        p = h / jnp.maximum(n, 1e-12)
        plogp = jnp.where(h > 0, p * jnp.log(jnp.maximum(p, 1e-12)), 0.0)
        return -(jnp.squeeze(n, axis) * plogp.sum(axis))
    if impurity == "variance":
        w, wy, wy2 = (jnp.take(h, i, axis=axis) for i in range(3))
        return jnp.maximum(wy2 - jnp.where(w > 0, wy * wy / jnp.maximum(w, 1e-12), 0.0), 0.0)
    raise ValueError(f"unknown impurity {impurity!r}")


def split_gain(left: jnp.ndarray, right: jnp.ndarray, impurity: str,
               axis: int = -1) -> jnp.ndarray:
    parent = left + right
    return (weighted_impurity(parent, impurity, axis)
            - weighted_impurity(left, impurity, axis)
            - weighted_impurity(right, impurity, axis))


# ---------------------------------------------------------------------------
# Numerical — faithful Alg. 1 scan backend
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_leaves", "impurity", "task"))
def best_numeric_split_scan(
    vals_sorted: jnp.ndarray,    # (n,) float32, ascending
    leaf_sorted: jnp.ndarray,    # (n,) int32 in [0, L], 0 = closed
    w_sorted: jnp.ndarray,       # (n,) float32 bag weights
    stats_sorted: jnp.ndarray,   # (n, S) float32 row stats
    cand_leaf: jnp.ndarray,      # (L+1,) bool — feature is candidate for leaf
    num_leaves: int,             # L (static)
    impurity: str = "gini",
    task: str = "classification",
    min_records: float = 1.0,
    h_init: jnp.ndarray | None = None,   # (L+1, S) prefix from earlier row shards
    v_init: jnp.ndarray | None = None,   # (L+1,)  last in-bag value in earlier shards
    totals: jnp.ndarray | None = None,   # (L+1, S) GLOBAL per-leaf totals
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Alg. 1 verbatim: one streaming pass, H ∈ (L+1, S) carried.

    The optional h_init/v_init/totals let a row shard resume the scan exactly
    where the previous (presorted-order) shard left off — the 2-D sharding
    extension (DESIGN.md §5).  Returns (best_gain, best_threshold), each
    (L+1,); entry 0 (closed) unused.
    """
    L1, s_dim = num_leaves + 1, stats_sorted.shape[-1]
    if totals is None:
        totals = jax.ops.segment_sum(
            jnp.where((w_sorted > 0)[:, None], stats_sorted, 0.0),
            leaf_sorted, num_segments=L1)
    cnt = count_fn(task)

    def step(carry, xs):
        H, v, best_s, best_t = carry
        a, h, w, srow = xs
        active = (h > 0) & cand_leaf[h] & (w > 0)
        Hh, vh = H[h], v[h]
        tau = (a + vh) * 0.5
        left, right = Hh, totals[h] - Hh
        ok = active & (a > vh) & jnp.isfinite(vh) \
            & (cnt(left) >= min_records) & (cnt(right) >= min_records)
        g = jnp.where(ok, split_gain(left, right, impurity), NEG)
        better = g > best_s[h]
        best_s = best_s.at[h].set(jnp.where(better, g, best_s[h]))
        best_t = best_t.at[h].set(jnp.where(better, tau, best_t[h]))
        H = H.at[h].add(jnp.where(active, srow, 0.0))
        v = v.at[h].set(jnp.where(active, a, vh))
        return (H, v, best_s, best_t), None

    init = (jnp.zeros((L1, s_dim), jnp.float32) if h_init is None else h_init,
            jnp.full((L1,), jnp.inf, jnp.float32) if v_init is None else v_init,
            jnp.full((L1,), NEG), jnp.zeros((L1,), jnp.float32))
    # v init=+inf makes (a > v) False for the first in-bag row of each leaf,
    # after which v tracks the last in-bag value — the paper's v_h.
    (H, v, best_s, best_t), _ = jax.lax.scan(
        step, init, (vals_sorted, leaf_sorted, w_sorted, stats_sorted))
    del H, v
    return best_s, best_t


# ---------------------------------------------------------------------------
# Numerical — sorted-segment backend (TPU-native, exact)
# ---------------------------------------------------------------------------

def _segmented_cummax_exclusive(x: jnp.ndarray, is_start: jnp.ndarray) -> jnp.ndarray:
    """Exclusive running max within segments (reset at is_start)."""
    def combine(a, b):
        (va, ba), (vb, bb) = a, b
        return jnp.where(bb, vb, jnp.maximum(va, vb)), ba | bb
    inc, _ = jax.lax.associative_scan(combine, (x, is_start))
    exc = jnp.concatenate([NEG[None], inc[:-1]])
    return jnp.where(is_start, NEG, exc)


def _segmented_cummax_exclusive_2d(x: jnp.ndarray,
                                   is_start: jnp.ndarray) -> jnp.ndarray:
    """`_segmented_cummax_exclusive` batched along axis 0 (scan on axis 1)."""
    m = x.shape[0]
    def combine(a, b):
        (va, ba), (vb, bb) = a, b
        return jnp.where(bb, vb, jnp.maximum(va, vb)), ba | bb
    inc, _ = jax.lax.associative_scan(combine, (x, is_start), axis=1)
    exc = jnp.concatenate([jnp.full((m, 1), NEG), inc[:, :-1]], axis=1)
    return jnp.where(is_start, NEG, exc)


@functools.partial(jax.jit, static_argnames=("num_leaves", "impurity", "task"))
def best_numeric_split_segment(
    vals_sorted: jnp.ndarray,
    leaf_sorted: jnp.ndarray,
    w_sorted: jnp.ndarray,
    stats_sorted: jnp.ndarray,
    cand_leaf: jnp.ndarray,
    num_leaves: int,
    impurity: str = "gini",
    task: str = "classification",
    min_records: float = 1.0,
    h_init: jnp.ndarray | None = None,
    v_init: jnp.ndarray | None = None,
    totals: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact vectorized supersplit: counting-sort by leaf + segmented cumsum."""
    L1 = num_leaves + 1
    n = vals_sorted.shape[0]
    cnt = count_fn(task)

    order = jnp.argsort(leaf_sorted, stable=True)          # leaves contiguous,
    lf = leaf_sorted[order]                                 # value-sorted inside
    a = vals_sorted[order]
    w = w_sorted[order]
    inbag = (w > 0) & (lf > 0)
    contrib = jnp.where(inbag[:, None], stats_sorted[order], 0.0)

    cum = jnp.cumsum(contrib, axis=0)
    cum_excl = cum - contrib
    is_start = jnp.concatenate([jnp.ones((1,), bool), lf[1:] != lf[:-1]])
    start_idx = jax.lax.cummax(jnp.where(is_start, jnp.arange(n), -1))
    left = cum_excl - cum_excl[start_idx]                   # per-leaf exclusive prefix
    if h_init is not None:
        left = left + h_init[lf]                            # earlier-shard prefix

    if totals is None:
        assert h_init is None, "row-sharded call must pass GLOBAL totals"
        totals = jax.ops.segment_sum(contrib, lf, num_segments=L1)
    right = totals[lf] - left

    pv = _segmented_cummax_exclusive(jnp.where(inbag, a, NEG), is_start)
    if v_init is not None:
        vi = jnp.where(jnp.isfinite(v_init), v_init, NEG)
        pv = jnp.maximum(pv, vi[lf])
    ok = inbag & cand_leaf[lf] & (a > pv) & jnp.isfinite(pv) \
        & (cnt(left) >= min_records) & (cnt(right) >= min_records)
    gain = jnp.where(ok, split_gain(left, right, impurity), NEG)
    tau = (a + pv) * 0.5

    best_s = jax.ops.segment_max(gain, lf, num_segments=L1)
    best_s = jnp.maximum(best_s, NEG)  # segment_max of empty segment -> -inf already
    # first row achieving the max (scan-order tie-breaking)
    hit = gain >= best_s[lf]
    first = jax.ops.segment_min(jnp.where(hit, jnp.arange(n), n), lf, num_segments=L1)
    best_t = jnp.where(first < n, tau[jnp.minimum(first, n - 1)], 0.0)
    return best_s, best_t


NUMERIC_BACKENDS = {
    "scan": best_numeric_split_scan,
    "segment": best_numeric_split_segment,
}


# ---------------------------------------------------------------------------
# Numerical — leaf-ordered backend (the fused level step's fast path)
# ---------------------------------------------------------------------------
#
# Identical semantics to `best_numeric_split_segment`, but the caller hands
# rows already in (leaf, value)-sorted order, so the per-level counting sort
# (the dominant per-column cost at scale) disappears.  The fused tree
# builder maintains that order incrementally across levels: children of a
# leaf are stable partitions of the parent's contiguous block, an O(n)
# segmented-cumsum update instead of an O(n log n) sort (see tree.py).

def _segmented_first_max(gain: jnp.ndarray, tau: jnp.ndarray,
                         is_start: jnp.ndarray):
    """Inclusive segmented (max, argfirst) scan along the last axis: at each
    row, the best gain seen so far in its segment and the threshold of the
    FIRST row achieving it (scan-order tie-breaking, matching Alg. 1)."""
    def combine(a, b):
        (ga, ta, sa), (gb, tb, sb) = a, b
        take_b = sb | (gb > jnp.where(sb, NEG, ga))
        return (jnp.where(take_b, gb, ga), jnp.where(take_b, tb, ta), sa | sb)
    bs, bt, _ = jax.lax.associative_scan(combine, (gain, tau, is_start),
                                         axis=-1)
    return bs, bt


def best_numeric_split_leaf_ordered(
    vals: jnp.ndarray,           # (m, n) float32, (leaf, value)-sorted rows
    lf_pos: jnp.ndarray,         # (n,) int32 leaf id PER POSITION (shared)
    inbag: jnp.ndarray,          # (m, n) bool: w > 0 & leaf open, per column
    stats: jnp.ndarray,          # (S, m, n) row stats in leaf order
    cand_leaf: jnp.ndarray,      # (m, L+1) bool
    num_leaves: int,
    impurity: str = "gini",
    task: str = "classification",
    min_records: float = 1.0,
    totals: jnp.ndarray | None = None,     # (L+1, S) shared per-leaf totals
    row_counts: jnp.ndarray | None = None,  # (L+1,) rows per leaf (all rows)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact all-columns supersplit over pre-leaf-ordered rows.

    Natively batched over the column axis (no vmap, no per-column sort, no
    scatter-add in the hot path).  Because every column holds the same
    multiset of rows counting-sorted by the same leaf ids, the block
    structure is column-independent: `lf_pos` is the ONE leaf-of-position
    array shared by all columns, and block starts/ends derive from the one
    `row_counts` histogram.

    When `totals` is None the per-leaf totals are reduced from each
    column's own row order (bit-matching the `segment` backend); passing
    the level's shared totals saves the reduction — exact for
    classification, where stats are integer-valued bag counts.  Returns
    (best_gain, best_threshold), each (m, L+1).
    """
    m, n = vals.shape
    L1 = num_leaves + 1
    cnt = count_fn(task, axis=0)
    if row_counts is None:
        row_counts = jax.ops.segment_sum(
            jnp.ones((n,), jnp.int32), lf_pos, num_segments=L1)

    contrib = jnp.where(inbag[None], stats, 0.0)             # (S, m, n)
    cum = jnp.cumsum(contrib, axis=2)
    cum_excl = cum - contrib
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), lf_pos[1:] != lf_pos[:-1]])   # (n,) shared
    start_idx = jax.lax.cummax(jnp.where(is_start, jnp.arange(n), -1))
    left = cum_excl - cum_excl[:, :, start_idx]              # excl prefix
    if totals is None:
        flat = (jnp.arange(m)[:, None] * L1 + lf_pos[None]).reshape(-1)
        totals_cols = jax.vmap(lambda c: jax.ops.segment_sum(
            c.reshape(-1), flat, num_segments=m * L1,
            indices_are_sorted=True))(contrib).reshape(-1, m, L1)
        parent = totals_cols[:, :, lf_pos]                   # (S, m, n)
    else:
        parent = totals.T[:, lf_pos][:, None, :]             # shared (S,1,n)
    right = parent - left

    is_start_b = jnp.broadcast_to(is_start[None], (m, n))
    pv = _segmented_cummax_exclusive_2d(
        jnp.where(inbag, vals, NEG), is_start_b)
    ok = inbag & cand_leaf[:, lf_pos] & (vals > pv) & jnp.isfinite(pv) \
        & (cnt(left) >= min_records) & (cnt(right) >= min_records)
    # parent impurity is recomputed from left + right per row, NOT from the
    # gathered per-leaf totals: the values agree, but evaluating the
    # impurity at a different array shape can flip the last ulp of
    # transcendentals (entropy's log), and the reference backend computes
    # it exactly this way
    gain = jnp.where(ok, split_gain(left, right, impurity, axis=0), NEG)
    tau = (vals + pv) * 0.5

    # Materialize gain/tau before the log-depth scan: without the barrier
    # XLA re-fuses (and so re-computes) the whole producer chain into every
    # scan level — a ~6x blowup measured on CPU.
    gain, tau = jax.lax.optimization_barrier((gain, tau))
    bs, bt = _segmented_first_max(gain, tau, is_start_b)
    # each leaf's best sits at its block's LAST row; block ends follow from
    # the (column-independent) leaf histogram — a gather, not a scatter
    end_pos = jnp.maximum(jnp.cumsum(row_counts) - 1, 0)     # (L+1,)
    occupied = row_counts > 0
    best_s = jnp.where(occupied[None, :], bs[:, end_pos], NEG)
    best_t = jnp.where(occupied[None, :], bt[:, end_pos], 0.0)
    return best_s, best_t


# ---------------------------------------------------------------------------
# Numerical — PLANET-style histogram (approximate) mode
# ---------------------------------------------------------------------------

def best_numeric_split_histogram(
    table: jnp.ndarray,          # (L+1, S, B) per-leaf (stat × bin) table
    cand_leaf: jnp.ndarray,      # (L+1,) bool
    impurity: str = "gini",
    task: str = "classification",
    min_records: float = 1.0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Approximate supersplit: score only the B−1 bucket boundaries.

    The PLANET-style contrast baseline to the paper's exact search
    (`split_mode="hist"`): the numeric column was quantized once at presort
    time into <= B quantile buckets (presort.quantize_edges), every level
    builds the per-leaf (bin × stat) count `table` with the SAME scatter-add
    machinery as the categorical path (`feature_count_tables` / the Pallas
    `feat_hist` kernel), and this scorer enumerates prefix cuts in bucket
    order — no reordering, buckets are already value-sorted, which is the
    only difference from `best_categorical_split_from_table`.

    Returns (best_gain (L+1,), best_cut (L+1,) float32) — best_cut is the
    winning BIN INDEX b (a cut keeps bins <= b left), not a float
    threshold: the level program never touches the float edges (the bin
    cache is its only per-row numeric input, DESIGN.md §6), and the host
    decodes `threshold = edges[col, b]` when recording the node, which
    reproduces the scored partition exactly (`bin <= b  <=>  x <=
    edges[b]`).  Empty buckets (duplicate edges) give zero-gain duplicate
    cuts and are never selected over a populated boundary.
    """
    totals = table.sum(2)                                   # (L+1, S)
    cnt = count_fn(task, axis=1)
    prefix = jnp.cumsum(table, axis=2)                      # cut after bin b
    left = prefix[:, :, :-1]                                # cuts 0..B-2
    right = totals[:, :, None] - left
    ok = (cnt(left) >= min_records) & (cnt(right) >= min_records) \
        & cand_leaf[:, None]
    gains = jnp.where(ok, split_gain(left, right, impurity, axis=1),
                      NEG)                                  # (L+1, B-1)
    best_cut = jnp.argmax(gains, axis=1)                    # first max
    best_gain = jnp.take_along_axis(gains, best_cut[:, None], axis=1)[:, 0]
    best_cut = jnp.where(jnp.isfinite(best_gain), best_cut, 0)
    return best_gain, best_cut.astype(jnp.float32)


def feature_count_tables(
    bin_of: jnp.ndarray,         # (m, n) packed bucket ids (uint8/uint16)
    leaf_ids: jnp.ndarray,       # ([T,] n) int32 scatter slots, 0 = discard
    w: jnp.ndarray,              # ([T,] n) float32 bag weights
    stats: jnp.ndarray,          # ([T,] n, S) row stats
    num_slots: int,              # table width minus one (slots 1..num_slots)
    num_bins: int,
    labels: jnp.ndarray | None = None,   # ([T,] n) int class ids, or None
) -> jnp.ndarray:
    """([T,] m, num_slots+1, S, B) per-leaf bin tables for ALL m features
    in ONE scatter over the flat ([tree,] feature, slot, stat, bin) index
    space.

    This is the jnp twin of the Pallas `feat_hist` kernel (kernels/ops
    .feature_tables): both accumulate each row's stat contribution into
    every feature's (slot, bin) cell in row order, so the two backends
    produce the same tables (bit-identical for the integer-valued
    classification stats).  The single flat segment_sum replaces the old
    per-column vmap of `categorical_count_table` — one scatter pass over
    the whole bin cache instead of m dispatched column scatters.

    `leaf_ids` are pre-mapped scatter SLOTS, not necessarily raw leaf ids:
    the subtraction path (level/engines.py) passes the packed build-leaf
    slots with derive-leaf rows mapped to the discarded slot 0.

    Two update layouts, chosen by the task the caller knows statically:

      * class path (`labels` given, classification): a row's stats are
        `one_hot(label) · w`, so it makes ONE update per column — `w` at
        its own class's stat — in place of S updates of which S − 1 add
        0.0.  The updates are (T, m, n); the sums, and their order per
        cell, are those of the plane path, so the tables are bit-equal.
      * plane path (`labels` None, regression's S = 3 nonzero stats): one
        update per (column, stat), (T, m, S, n) with rows minor (see the
        note on the stat axis above).

    A leading tree axis T on `leaf_ids`/`w`/`stats`/`labels` (the bins are
    shared; `labels` may omit it) folds into the same flat index space.
    A vmap would give a scatter with a batch dimension, whose compile time
    for a v5e grows with n: 91 s against 24 s flat for T = 4 at n = 2^18,
    and 414 s at 2^20.
    """
    batched = leaf_ids.ndim == 2
    if not batched:
        leaf_ids, w, stats = leaf_ids[None], w[None], stats[None]
    T, n = leaf_ids.shape
    m = bin_of.shape[0]
    W = num_slots + 1
    S = stats.shape[-1]
    if labels is not None:
        labels = jnp.broadcast_to(labels, (T, n)).astype(jnp.int32)
    if T * m * W * S * num_bins >= 2 ** 31:   # int32 flat index overflow
        return jax.vmap(lambda lf, ww, st, *lb: feature_count_tables(
            bin_of, lf, ww, st, num_slots, num_bins, *lb))(
            leaf_ids, w, stats, *([] if labels is None else [labels]))
    inbag = (w > 0) & (leaf_ids > 0)                        # (T, n)
    tree_feat = (jnp.arange(T, dtype=jnp.int32)[:, None] * m
                 + jnp.arange(m, dtype=jnp.int32)[None, :])  # (T, m)
    bins = bin_of.astype(jnp.int32)[None]                   # (1, m, n)
    if labels is not None:
        # one_hot(label, S) is all zero off [0, S): so is the update
        inbag = inbag & (labels >= 0) & (labels < S)
        contrib = jnp.where(inbag, w, 0.0)                  # (T, n)
        slot_stat = leaf_ids.astype(jnp.int32) * S + labels  # (T, n)
        flat = ((tree_feat[:, :, None] * (W * S)
                 + slot_stat[:, None]) * num_bins + bins)   # (T, m, n)
        updates = jnp.broadcast_to(contrib[:, None], (T, m, n))
    else:
        contrib = jnp.where(inbag[:, None], jnp.swapaxes(stats, 1, 2),
                            0.0)                            # (T, S, n)
        slot_stat = (leaf_ids.astype(jnp.int32)[:, None] * S
                     + jnp.arange(S, dtype=jnp.int32)[None, :, None])
        flat = ((tree_feat[:, :, None, None] * (W * S)
                 + slot_stat[:, None]) * num_bins
                + bins[:, :, None, :])                      # (T, m, S, n)
        updates = jnp.broadcast_to(contrib[:, None], (T, m, S, n))
    table = jax.ops.segment_sum(
        updates.reshape(-1), flat.reshape(-1),
        num_segments=T * m * W * S * num_bins)
    table = table.reshape(T, m, W, S, num_bins)
    return table if batched else table[0]


# ---------------------------------------------------------------------------
# Categorical — count tables + Breiman ordering (paper §2.4, SM)
# ---------------------------------------------------------------------------

def categorical_count_table(
    x_col: jnp.ndarray,          # (n,) int32 category values
    leaf_of: jnp.ndarray,        # (n,) int32 in [0, L]
    w: jnp.ndarray,              # (n,) float32
    stats: jnp.ndarray,          # (n, S)
    num_leaves: int,
    arity: int,
) -> jnp.ndarray:
    """The paper's 'attribute value x class -> count' table, (L+1, S, V)."""
    L1 = num_leaves + 1
    S = stats.shape[-1]
    inbag = (w > 0) & (leaf_of > 0)
    contrib = jnp.where(inbag[None], stats.T, 0.0)           # (S, n)
    flat = ((leaf_of[None] * S + jnp.arange(S)[:, None]) * arity
            + x_col[None])                                   # (S, n)
    table = jax.ops.segment_sum(contrib.reshape(-1), flat.reshape(-1),
                                num_segments=L1 * S * arity)
    return table.reshape(L1, S, arity)


def best_categorical_split_from_table(
    table: jnp.ndarray,          # (L+1, S, V) per-leaf count table
    cand_leaf: jnp.ndarray,      # (L+1,) bool
    impurity: str = "gini",
    task: str = "classification",
    min_records: float = 1.0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Breiman ordering + ordered prefix cuts on a prebuilt count table.

    Shared scoring for the jnp path (`best_categorical_split`) and the
    Pallas table-kernel path (kernels/ops.categorical_tables) — the
    table layout is identical, so the two backends give identical splits.

    Args:
      table:     (L+1, S, V) per-(leaf, category) stat sums — bag-weighted
                 one-hot class counts (S = C, classification) or
                 [w, wy, wy²] (S = 3, regression).  Row 0 (the closed-leaf
                 sentinel) is ignored.  V may include padded categories;
                 they are empty (all-zero) and sort last, so cuts only
                 enumerate populated prefixes.
      cand_leaf: (L+1,) bool — leaves for which this feature is a
                 candidate; others return gain −inf.
      impurity/task/min_records: as for the numeric engines; both children
                 of a reported cut have >= min_records in-bag weight.

    Categories are ordered per leaf by the Breiman metric — P(last class |
    v) for classification (exact for binary), mean(y | v) for regression
    (exact for L2) — and only the V−1 ordered prefix cuts are scored: the
    optimal subset split for those cases at O(V log V) instead of 2^V.

    Returns (best_gain (L+1,), mask (L+1, V) bool); mask True sends the
    category to the LEFT child.  Under `tree.build_forest` this whole
    search is vmapped over a leading tree axis.
    """
    arity = table.shape[2]
    totals = table.sum(2)                                   # (L+1, S)
    cnt = count_fn(task, axis=1)

    tc = cnt(table)                                         # (L+1, V) counts
    if task == "classification":
        metric = table[:, -1] / jnp.maximum(tc, 1e-12)
    else:
        metric = table[:, 1] / jnp.maximum(tc, 1e-12)
    # Put empty categories last so cuts enumerate only populated prefixes.
    metric = jnp.where(tc > 0, metric, jnp.inf)
    order = jnp.argsort(metric, axis=1, stable=True)        # (L+1, V)
    sorted_table = jnp.take_along_axis(table, order[:, None, :], axis=2)
    prefix = jnp.cumsum(sorted_table, axis=2)               # inclusive: cut after pos v
    left = prefix[:, :, :-1]                                # cuts 0..V-2
    right = totals[:, :, None] - left
    ok = (cnt(left) >= min_records) & (cnt(right) >= min_records) \
        & cand_leaf[:, None]
    gains = jnp.where(ok, split_gain(left, right, impurity, axis=1),
                      NEG)                                  # (L+1, V-1)

    best_cut = jnp.argmax(gains, axis=1)                    # first max: argmax picks first
    best_gain = jnp.take_along_axis(gains, best_cut[:, None], axis=1)[:, 0]
    # category v goes left iff its sorted position is <= the cut.  The
    # sort is stable, so positions follow (metric, category id) order:
    # compare each category with the one at the cut instead of inverting
    # the permutation (a second sort, slow to compile on a TPU)
    at_cut = jnp.take_along_axis(order, best_cut[:, None], axis=1)   # (L+1, 1)
    m_cut = jnp.take_along_axis(metric, at_cut, axis=1)
    v = jnp.arange(arity)[None, :]
    mask = (metric < m_cut) | ((metric == m_cut) & (v <= at_cut))
    return best_gain, mask


@functools.partial(jax.jit, static_argnames=("num_leaves", "arity", "impurity", "task"))
def best_categorical_split(
    x_col: jnp.ndarray,          # (n,) int32 category values
    leaf_of: jnp.ndarray,        # (n,) int32 in [0, L]
    w: jnp.ndarray,              # (n,) float32
    stats: jnp.ndarray,          # (n, S)
    cand_leaf: jnp.ndarray,      # (L+1,) bool
    num_leaves: int,
    arity: int,
    impurity: str = "gini",
    task: str = "classification",
    min_records: float = 1.0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Best subset split x ∈ C per open leaf, one pass.

    Builds the (leaf × category × stat) count table the paper describes for
    categorical attributes, then orders categories per leaf by the Breiman
    metric (P(last class | v) for classification — exact for binary
    classification; mean(y|v) for regression — exact for L2) and scans the
    ordered prefix cuts.

    Returns (best_gain (L+1,), best_mask (L+1, arity) bool) — mask True means
    the category goes to the LEFT child.
    """
    table = categorical_count_table(x_col, leaf_of, w, stats, num_leaves, arity)
    return best_categorical_split_from_table(
        table, cand_leaf, impurity, task, min_records)
