"""DRF distribution on the TPU mesh (paper §2 worker topology → shard_map).

The mesh machinery now lives in `repro.core.level.sharded` as
`SplitEngine`s — the same engine objects plug into the ONE level plan that
local training uses, so sharded training inherits the multi-tree batch
axis, early-finish masking and device-resident pruning of
`tree.build_forest` (DESIGN.md §5/§7).  This module keeps the historical
factory entry points (each returns the corresponding engine; the engines
are also callable with the original `supersplit_fn` signatures) plus the
pieces that never were engines: the 1-bit condition broadcast and the
dry-run level step.

Topology mapping (DESIGN.md §5):

  * "model" axis  = the splitters: feature columns are sharded over it, each
    device searching optimal splits only on its own columns (paper: "each
    worker is assigned to a subset of columns ... read sequentially").
  * "data" axis   = row shards — range-partitions of the PRESORTED order
    for the exact engine (beyond-paper 2-D extension), plain row order for
    the histogram/categorical table engines.
  * partial supersplit merge = the gains all_gather / table psum (the
    paper's tree builder "comparing the answers of the splitters").
  * condition evaluation    = 1 bit per sample, psum over "model" (only the
    winning column's owner contributes) — the paper's "Dn bits in D
    allreduce" per tree.

All engines are shard_map'd and composable under jit, so the SAME code
lowers for the 16×16 single-pod and (2,16,16) multi-pod production meshes
in launch/dryrun.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import splits
from repro.core.level.sharded import (ShardedCategorical,  # noqa: F401
                                      ShardedExactNumeric,
                                      ShardedHistNumeric, _shmap)


def make_column_sharded_supersplit(mesh, feature_axis: str = "model"):
    """Exact engine, columns sharded over `feature_axis`, rows replicated —
    the paper's splitter memory layout ("Sliq/R and DRF duplicate the class
    list in each worker")."""
    return ShardedExactNumeric(mesh=mesh, feature_axis=feature_axis,
                               row_axis=None)


def make_2d_sharded_supersplit(mesh, feature_axis: str = "model",
                               row_axis: str = "data",
                               backend: str = "segment"):
    """Exact engine with BOTH axes sharded (beyond-paper extension): row
    shards resume the presorted scan from the previous shard's
    all_gathered histogram/value state — see
    `level.sharded.ShardedExactNumeric`."""
    return ShardedExactNumeric(mesh=mesh, feature_axis=feature_axis,
                               row_axis=row_axis, backend=backend)


def make_hist_sharded_supersplit(mesh, feature_axis: str = "model",
                                 row_axis="data"):
    """Histogram engine for `split_mode="hist"`: per-shard (bin × stat)
    tables merged by ONE psum of (L+1)·B·S floats per column — the paper's
    network-complexity contrast with the exact all_gather, executable side
    by side (DESIGN.md §6)."""
    return ShardedHistNumeric(mesh=mesh, feature_axis=feature_axis,
                              row_axis=row_axis)


def make_categorical_sharded_supersplit(mesh, feature_axis: str = "model",
                                        row_axis="data"):
    """Categorical table engine under the mesh (order-free psum merge);
    requires m_cat divisible by the feature-axis size."""
    return ShardedCategorical(mesh=mesh, feature_axis=feature_axis,
                              row_axis=row_axis)


# ---------------------------------------------------------------------------
# 1-bit condition broadcast (Alg. 2 steps 5/7) under the mesh
# ---------------------------------------------------------------------------

def make_sharded_evaluate(mesh, feature_axis: str = "model"):
    """Winning-condition evaluation: the owner of the winning column computes
    the bit; a psum over the splitter axis broadcasts it (n bits per level —
    the paper's Table 1 network row for DRF)."""

    def fn(num_cols, leaf_of, feat_of_leaf, thr_of_leaf, m_num):
        # num_cols: (m_num, n) raw columns sharded over feature_axis.
        def local(cols, leaf_of, feat_of_leaf, thr_of_leaf):
            k = jax.lax.axis_index(feature_axis)
            mloc = cols.shape[0]
            lo = k * mloc
            f = feat_of_leaf[leaf_of]                       # global feature id
            mine = (f >= lo) & (f < lo + mloc)
            jloc = jnp.clip(f - lo, 0, mloc - 1)
            x = cols[jloc, jnp.arange(cols.shape[1])]
            bit = mine & (x <= thr_of_leaf[leaf_of])
            return jax.lax.psum(bit.astype(jnp.uint8), feature_axis)

        sharded = _shmap(
            local, mesh,
            in_specs=(P(feature_axis, None), P(None), P(None), P(None)),
            out_specs=P(None))
        return sharded(num_cols, leaf_of, feat_of_leaf, thr_of_leaf) > 0

    return fn


# ---------------------------------------------------------------------------
# One DRF level as a single jittable step (the dry-run / roofline workload)
# ---------------------------------------------------------------------------

def drf_level_step_fn(mesh, *, num_leaves: int, num_classes: int,
                      impurity: str = "gini", backend: str = "segment",
                      feature_axis: str = "model", row_axis: str = "data"):
    """Build the jittable 'one depth level of DRF' step used by launch/dryrun.

    Inputs (see launch/specs): sorted_vals/sorted_idx (m, n) sharded
    (feature_axis, row_axis); leaf_of (n,), labels (n,), w (n,) sharded
    (row_axis,).  Output: per-(feature, leaf) best gains/thresholds plus the
    winning per-leaf split — i.e. Alg. 2 step 3 for one level, end to end.
    """
    sup = make_2d_sharded_supersplit(mesh, feature_axis, row_axis, backend)

    def step(sorted_vals, sorted_idx, leaf_of, labels, w, cand):
        stats = splits.row_stats(labels, w, num_classes, "classification")
        gains, thr = sup(sorted_vals, sorted_idx, leaf_of, w, stats, cand,
                         num_leaves, impurity, "classification", 1.0)
        best_feat = jnp.argmax(gains, axis=0)               # (L+1,)
        best_gain = jnp.max(gains, axis=0)
        best_thr = jnp.take_along_axis(thr, best_feat[None], 0)[0]
        return best_feat.astype(jnp.int32), best_gain, best_thr

    return step
