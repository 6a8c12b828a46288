"""jit'd wrappers around the Pallas kernels.

These adapt the tree builder's (sorted_idx, leaf_of, w, labels) state to the
kernels' pre-gathered blocked layout, handle padding (row blocks, leaf-lane
alignment, arity blocks), and select interpret mode automatically off-TPU.
The `"kernel"` numeric backend used by `tree.TreeParams(backend="kernel")`
lands here, as does the kernel categorical path of the fused level step.

Both entry points take the stat dimension from the caller (`num_classes`):
deriving it from `labels.max()` would be a per-call device->host sync in the
middle of the level loop (and is impossible under jit).  The seed behaviour
is kept as an eager-only fallback when `num_classes` is omitted.

Both entry points also batch over a leading TREE axis: `tree.build_forest`
vmaps them over per-tree (leaf_of, w) state, and `pallas_call`'s batching
rule folds that axis into the kernel grid — one kernel launch for the
whole tree batch, bit-identical per tree to the unbatched call
(tests/test_forest_batch.py exercises this through the `kernel` backend).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import splits
from repro.kernels import feat_hist, split_scan


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_rows(n: int, bn: int) -> int:
    return (-n) % bn


# --- interpret-mode compile-cost bounds -----------------------------------
#
# Off-TPU the Pallas kernels run in interpret mode, where the sequential
# row-block grid is UNROLLED at trace time: the lowered program contains one
# copy of the kernel body per block, so with the default bn=256 a fused
# level step at n≫1M would emit thousands of body copies and compile
# pathologically (ROADMAP "kernel-backend compile cost at scale").  The
# plan below bounds the unrolled block count by growing the block size —
# the body stays ONE set of ops, only operand shapes grow — and, for the
# split_scan kernel only (whose in-block prefix is a Bn×Bn triangular
# matmul, O(bn²) memory/work), gates to the exact jnp `segment` engine once
# the grown block would exceed _MAX_INTERPRET_BN.  On TPU nothing changes:
# the grid is a real sequential grid, not an unroll.

_MAX_INTERPRET_ROW_BLOCKS = 64
_MAX_INTERPRET_BN = 2048


def _interpret_grid_plan(n: int, bn: int,
                         quadratic: bool = False) -> tuple[int, int, bool]:
    """(bn_eff, nblocks, gated) bounding the interpret-mode grid.

    nblocks <= _MAX_INTERPRET_ROW_BLOCKS always; `gated=True` (only
    possible with quadratic=True) means the caller must fall back to a
    non-Pallas exact engine instead.
    """
    blocks = max(1, -(-n // bn))
    if blocks <= _MAX_INTERPRET_ROW_BLOCKS:
        return bn, blocks, False
    bn_eff = -(-n // _MAX_INTERPRET_ROW_BLOCKS)
    bn_eff += (-bn_eff) % 128                  # keep lane alignment
    if quadratic and bn_eff > _MAX_INTERPRET_BN:
        return bn, blocks, True
    return bn_eff, max(1, -(-n // bn_eff)), False


def _stat_dim(labels, num_classes, task: str) -> int:
    if task != "classification":
        return 3
    if num_classes is None:
        # eager-only fallback (device sync); pass num_classes to avoid it
        return max(int(labels.max()) + 1, 2)
    return max(int(num_classes), 2)


def split_scan_supersplit(sorted_vals, sorted_idx, leaf_of, w, labels,
                          cand, Lp, impurity="gini", task="classification",
                          min_records=1.0, bn=256, interpret=None,
                          num_classes=None):
    """All-columns supersplit via the Pallas kernel.

    sorted_vals/sorted_idx: (m, n); cand: (m, Lp+1) bool;
    returns (gain (m, Lp+1), thr (m, Lp+1)) matching the jnp backends.
    """
    if interpret is None:
        interpret = not _on_tpu()
    m, n = sorted_vals.shape
    L1 = Lp + 1
    s_dim = _stat_dim(labels, num_classes, task)

    if interpret:
        bn, _, gated = _interpret_grid_plan(n, bn, quadratic=True)
        if gated:
            # n too large for a bounded-unroll Pallas interpret program:
            # answer with the exact vectorized jnp engine instead (same
            # split choices up to float summation order — the same
            # tolerance the kernel itself is held to vs the scan spec)
            stats = splits.row_stats(labels, w, s_dim, task)

            def per_col(v, s, c):
                return splits.best_numeric_split_segment(
                    v, leaf_of[s], w[s], stats[s], c, Lp, impurity, task,
                    min_records)
            return jax.vmap(per_col)(sorted_vals, sorted_idx, cand)

    leaf_g = leaf_of[sorted_idx]                      # (m, n)
    w_g = w[sorted_idx]
    y_g = labels[sorted_idx].astype(jnp.float32)

    pad = _pad_rows(n, bn)
    if pad:
        sorted_vals = jnp.pad(sorted_vals, ((0, 0), (0, pad)))
        leaf_g = jnp.pad(leaf_g, ((0, 0), (0, pad)))       # leaf 0 = closed
        w_g = jnp.pad(w_g, ((0, 0), (0, pad)))             # w 0 = skipped
        y_g = jnp.pad(y_g, ((0, 0), (0, pad)))

    # global per-leaf totals (cheap; exact "right" histograms) — the same
    # for every column, so reduced once over the unsorted rows
    st = splits.row_stats(labels, w.astype(jnp.float32), s_dim, task)
    st = jnp.where(((w > 0) & (leaf_of > 0))[:, None], st, 0.0)
    totals = jnp.broadcast_to(
        jax.ops.segment_sum(st, leaf_of, num_segments=L1), (m, L1, s_dim))

    return split_scan.split_scan_pallas(
        sorted_vals, leaf_g.astype(jnp.int32), w_g.astype(jnp.float32), y_g,
        cand.astype(jnp.float32), totals, L1=L1, s_dim=s_dim, bn=bn,
        impurity=impurity, task=task, min_records=float(min_records),
        interpret=interpret)


def categorical_tables(cat_cols, leaf_of, w, labels, *, V, Lp,
                       task="classification", bn=256, interpret=None,
                       num_classes=None):
    """Count tables (m_cat, Lp+1, S, V) via the Pallas table kernel.

    Any arity V is supported: the kernel tiles the category axis (values
    >= V never occur in the data, so padded lanes stay zero).
    """
    return _tables(cat_cols, leaf_of, w, labels, W=Lp + 1, V=V, task=task,
                   bn=bn, interpret=interpret, num_classes=num_classes)


def feature_tables(bin_of, leaf_ids, w, labels, *, B, W,
                   task="classification", bn=256, interpret=None,
                   num_classes=None):
    """Histogram tables (m, W, S, B) for ALL features in ONE pass over the
    row blocks, via the Pallas table kernel (`feat_hist`).

    bin_of: (m, n) bit-packed bucket ids; leaf_ids: (n,) scatter slots
    (0 = discard; raw leaf ids on the plain path, packed build slots on
    the subtraction path — see level/engines.py); W = slot-axis width.
    The jnp twin is `splits.feature_count_tables` (one flat segment_sum);
    the classification stats are integers, so the two agree bit for bit.
    """
    return _tables(bin_of, leaf_ids, w, labels, W=W, V=B, task=task, bn=bn,
                   interpret=interpret, num_classes=num_classes)


def _tables(x, leaf, w, labels, *, W, V, task, bn, interpret, num_classes):
    if interpret is None:
        interpret = not _on_tpu()
    m, n = x.shape
    s_dim = _stat_dim(labels, num_classes, task)
    if interpret:
        # bound the unrolled row-block count (body work per block is
        # linear in bn — the per-feature one-hot matmuls — so growing the
        # block never gates)
        bn, _, _ = _interpret_grid_plan(n, bn)
    pad = _pad_rows(n, bn)
    y = labels.astype(jnp.float32)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))       # value 0, but leaf 0 =
        leaf = jnp.pad(leaf, (0, pad))           # discarded anyway
        w = jnp.pad(w, (0, pad))                 # w 0 = skipped
        y = jnp.pad(y, (0, pad))
    return feat_hist.feat_hist_pallas(x, leaf, w, y, W=W, V=V, s_dim=s_dim,
                                      bn=bn, task=task, interpret=interpret)
