"""Dataset preparation: presorting of numerical attributes (paper §2.1).

"Consistently with existing works, we use presorting for numerical
attributes" — the single most expensive preparation step. Done once; every
tree and every depth level reuses it. On the distributed mesh the presort
is a sharded `argsort` per column (the paper's external sort becomes XLA's
distributed sort); rows of the sorted order are range-partitioned over the
"data" axis so each shard owns a contiguous slice of every sorted column.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=())
def presort_columns(num: jnp.ndarray) -> jnp.ndarray:
    """argsort each numerical column.

    Args:
      num: (n, m_num) float32.
    Returns:
      sorted_idx: (m_num, n) int32 — row indices in increasing value order,
      stable (ties keep original row order, making runs reproducible).
    """
    return jnp.argsort(num.T, axis=-1, stable=True).astype(jnp.int32)


def gather_sorted(num: jnp.ndarray, sorted_idx: jnp.ndarray) -> jnp.ndarray:
    """Materialize the sorted values: (m_num, n) float32."""
    return jnp.take_along_axis(num.T, sorted_idx, axis=-1)


# ---------------------------------------------------------------------------
# PLANET-style threshold buckets (the approximate contrast baseline)
# ---------------------------------------------------------------------------
#
# The paper's central claim is that DRF stays EXACT where PLANET-era systems
# quantize numeric columns into fixed bins.  `split_mode="hist"` reproduces
# that baseline inside the same fused level machinery: each numeric column
# is bucketed ONCE at presort time into <= num_bins quantile buckets, and
# every level scores only the bucket boundaries from per-leaf (bin × class)
# count tables (splits.best_numeric_split_histogram) instead of every
# midpoint between consecutive values.

@functools.partial(jax.jit, static_argnames=("num_bins",))
def quantize_edges(sorted_vals: jnp.ndarray, num_bins: int) -> jnp.ndarray:
    """Per-column bucket upper edges from the presorted values.

    Args:
      sorted_vals: (m_num, n) float32, each row ascending (gather_sorted).
      num_bins:    bucket count B (PLANET-style fixed budget, e.g. 255).
    Returns:
      edges: (m_num, B) float32 — edges[j, b] is the LARGEST value of
      column j falling in bucket b (equi-depth quantile positions, so every
      bucket holds ~n/B rows; edges[j, B-1] is the column max).  The bucket
      rule is  b(x) = number of lower edges strictly below x, so the
      candidate threshold for
      a cut after bucket b is exactly edges[j, b] with the tree's usual
      `x <= thr` condition — training-time bucket partitions and
      inference-time threshold partitions agree EXACTLY.  Duplicate edges
      (heavy ties / constant columns) simply leave empty buckets, which
      score as zero-gain cuts and are never selected.
    """
    n = sorted_vals.shape[1]
    pos = (jnp.arange(1, num_bins + 1) * n) // num_bins - 1   # (B,)
    pos = jnp.clip(pos, 0, n - 1)
    return sorted_vals[:, pos]


def bin_dtype(num_bins: int):
    """The bit-packed bucket-id dtype: bin ids live in [0, num_bins).

    uint8 up to 256 buckets (the PLANET-standard 255-bin budget included),
    uint16 past that — the bin cache is the ONLY per-row numeric state the
    hist-mode level program reads (DESIGN.md §6), so packing it is a 4x
    memory-traffic cut over the old int32 ids (and 4x over re-reading the
    float32 columns).
    """
    return jnp.uint8 if num_bins <= 256 else jnp.uint16


@jax.jit
def bin_columns(num: jnp.ndarray, edges: jnp.ndarray) -> jnp.ndarray:
    """Bucket id per row per column: (n, m_num) values -> (m_num, n) packed.

    bin_of[j, k] = searchsorted(edges[j, :-1], num[k, j], side="left"), i.e.
    the first bucket whose upper edge is >= the value; values above the
    column max (unseen at fit time) land in the last bucket.  The result is
    bit-packed (`bin_dtype`): uint8 for <= 256 buckets, uint16 beyond.
    """
    dt = bin_dtype(edges.shape[1])

    def per_col(v, e):
        return jnp.searchsorted(e[:-1], v, side="left").astype(dt)
    with jax.named_scope("presort.bin_columns"):
        return jax.vmap(per_col)(num.T, edges)


def quantize(num: jnp.ndarray, sorted_vals: jnp.ndarray,
             num_bins: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The full hist-mode bucket state from an existing presort.

    The one quantization recipe shared by `RandomForest.fit`,
    `GBTModel.fit` and `TabularDataset.quantize`.  Returns
    (bin_of (m_num, n) uint8/uint16 — see `bin_dtype`,
    edges (m_num, num_bins) float32).  `bin_of` is the device-resident bin
    cache every hist level reads; `edges` only decodes winning cut indices
    back to float thresholds on the HOST (tree.py), so no float32 column
    traffic remains inside the level program.
    """
    edges = quantize_edges(sorted_vals, num_bins)
    return bin_columns(num, edges), edges


# ---------------------------------------------------------------------------
# Chunked (out-of-core) quantization — DESIGN.md §8
# ---------------------------------------------------------------------------
#
# `quantize_edges` reads the fully presorted columns; for datasets that
# never fit in memory the SAME order-statistic edges are found by a
# multi-pass radix select over chunked column blocks: float32 values map
# to order-preserving uint32 keys, pass 1 histograms the top 16 key bits
# per column, and two refinement passes (8 bits each) narrow only the
# <= num_bins prefixes a quantile still needs — three sequential passes
# over the data, O(m·B) state, and edges that are BIT-EQUAL to
# `quantize_edges(gather_sorted(...))` (asserted by the streaming parity
# suite).  Caveats of the key order: NaNs are not supported, and a column
# mixing -0.0/+0.0 exactly at a quantile position may differ in the sign
# of the zero edge (the values still compare equal, so binning agrees).

_KEY_GROUPS = (16, 8, 8)            # bit-group widths, high to low


def _float_keys(block: np.ndarray) -> np.ndarray:
    """Order-preserving uint32 keys for a float32 block (same shape)."""
    b = np.ascontiguousarray(block, np.float32).view(np.uint32)
    return np.where(b & 0x80000000, ~b, b ^ 0x80000000).astype(np.uint32)


def _keys_to_float(keys: np.ndarray) -> np.ndarray:
    """Invert `_float_keys`: uint32 keys back to float32 values."""
    k = np.asarray(keys, np.uint32)
    b = np.where(k & 0x80000000, k ^ 0x80000000, ~k).astype(np.uint32)
    return b.view(np.float32)


def streaming_quantile_edges(chunks, n: int, m_num: int,
                             num_bins: int) -> np.ndarray:
    """Exact per-column quantile edges from chunked column blocks.

    Args:
      chunks:   re-iterable callable; each call returns an iterator of
                (c, m_num) float32 row blocks covering the n rows in
                order.  Iterated once per radix pass (3 passes).
      n/m_num:  total rows / numeric columns.
      num_bins: bucket budget B.
    Returns:
      edges (m_num, B) float32 — bit-equal to
      `quantize_edges(gather_sorted(num, presort_columns(num)), B)` (the
      in-memory recipe) at the same order-statistic positions
      pos = clip((arange(1, B+1)·n)//B − 1, 0, n−1).
    """
    assert n > 0 and m_num > 0
    pos = (np.arange(1, num_bins + 1, dtype=np.int64) * n) // num_bins - 1
    pos = np.clip(pos, 0, n - 1)
    rank = np.broadcast_to(pos + 1, (m_num, num_bins)).astype(np.int64)
    rank = rank.copy()                       # remaining rank inside prefix
    pref = np.zeros((m_num, num_bins), np.int64)   # resolved high bits
    done = 0
    for g, width in enumerate(_KEY_GROUPS):
        shift = 32 - done - width
        size = 1 << width
        if g == 0:
            counts = np.zeros((m_num, size), np.int64)
            for block in chunks():
                keys = _float_keys(block) >> np.uint32(shift)
                for j in range(m_num):
                    counts[j] += np.bincount(keys[:, j], minlength=size)
            for j in range(m_num):
                cum = np.cumsum(counts[j])
                gsel = np.searchsorted(cum, rank[j], side="left")
                rank[j] -= np.where(gsel > 0, cum[gsel - 1], 0)
                pref[j] = gsel
        else:
            # refine only the prefixes some quantile still needs
            uniq = [np.unique(pref[j]) for j in range(m_num)]
            P = max(len(u) for u in uniq)
            counts = np.zeros((m_num, P, size), np.int64)
            mask = size - 1
            # prefix -> its row in counts[j] (-1: no quantile needs it), a
            # table lookup per key instead of a binary search in uniq[j]
            slot = np.full(1 << done, -1, np.int64)
            for block in chunks():
                keys = _float_keys(block)
                hi = keys >> np.uint32(shift + width)
                sub = (keys >> np.uint32(shift)).astype(np.int64) & mask
                for j in range(m_num):
                    u = uniq[j]
                    slot[u] = np.arange(len(u))
                    idx = slot[hi[:, j]]
                    slot[u] = -1
                    match = idx >= 0
                    flat = idx[match] * size + sub[:, j][match]
                    counts[j] += np.bincount(
                        flat, minlength=P * size).reshape(P, size)
            for j in range(m_num):
                pi = np.searchsorted(uniq[j], pref[j])
                cum = np.cumsum(counts[j], axis=1)[pi]      # (B, size)
                gsel = (cum < rank[j][:, None]).sum(1)
                before = np.where(gsel > 0,
                                  cum[np.arange(num_bins), gsel - 1], 0)
                rank[j] -= before
                pref[j] = (pref[j] << width) | gsel
        done += width
    return _keys_to_float(pref.astype(np.uint32))


def bin_block(block: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """Host-side chunk binning: (c, m_num) float32 -> (m_num, c) packed.

    The numpy twin of `bin_columns` for RowSource chunk streams — same
    rule (`searchsorted(edges[j, :-1], v, side="left")`, values above the
    column max land in the last bucket), same `bin_dtype` packing, so a
    chunk-binned cache is bit-equal to the in-memory one.
    """
    m_num, B = edges.shape
    dt = np.uint8 if B <= 256 else np.uint16
    out = np.empty((m_num, block.shape[0]), dt)
    for j in range(m_num):
        out[j] = np.searchsorted(edges[j, :-1], block[:, j], side="left")
    return out
