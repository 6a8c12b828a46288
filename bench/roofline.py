"""The least HBM traffic of a level step, from shapes and trained trees.

A level step (`core/level/plan.py` `_fused_level_step[_batched]`) splits
every open leaf of a depth in one program.  Whatever implements it (the
`segment` scatter, the `feat_hist` kernel, the exact engines), it has at
least to read, for every in-bag row of a splittable leaf, that row's
value of each of the leaf's m' candidate columns, its leaf id and its
bag weight, and to write its new leaf id; in hist mode it also writes one
(bucket x stat) table per candidate column of each splittable leaf.
Out-of-bag rows, closed leaves and non-candidate columns need not be
touched, so the count is a lower bound: the level's time at the chip's
HBM bandwidth is the least time it could take.
"""
from __future__ import annotations

import numpy as np

import reference

ROW_BYTES = 12          # leaf id read + written, bag weight read


def value_bytes(mode: str, num_bins: int) -> int:
    """Bytes of one row's value of one column as the level reads it."""
    if mode == "exact":
        return 4                                   # float32
    return 1 if num_bins <= 256 else 2             # bucket id


def level_bytes(levels, *, m, mode, num_bins, num_classes) -> int:
    """Least bytes of the level steps of one tree.

    `levels`: (splittable leaves, their in-bag rows) per level step, as
    `tree_levels` gives them.
    """
    k = reference.m_prime(m)
    total = 0
    for leaves, rows in levels:
        total += rows * (k * value_bytes(mode, num_bins) + ROW_BYTES)
        if mode == "hist":
            total += leaves * k * num_bins * num_classes * 4
    return int(total)


def tree_levels(tree, X, seed, tree_idx, max_depth, min_records=1.0):
    """(splittable leaves, their in-bag rows) at each depth of a trained
    tree that a level step ran for, rows routed by the tree's own
    conditions."""
    t = reference.tree_arrays(tree)
    inbag = reference.bag_weights(seed, tree_idx, X.shape[0]) > 0
    out = []
    for depth, nodes, node_of in reference.walk(t, X, max_depth - 1):
        splittable = nodes[t["n_node"][nodes] >= 2 * min_records]
        if splittable.size == 0:
            break
        out.append((len(splittable),
                    int((np.isin(node_of, splittable) & inbag).sum())))
    return out
