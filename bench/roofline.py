"""The least HBM traffic of a level step, from shapes and trained trees.

A level step (`core/level/plan.py` `_fused_level_step[_batched]`) splits
every open leaf of a depth in one program.  Whatever implements it (the
`segment` scatter, the `feat_hist` kernel, the exact engines, dense or
sparse categorical tables), it has at least to read, for every in-bag
row of a splittable leaf, that row's value of each of the leaf's drawn
candidate columns (a bucket id or a float32 for a numeric column, an
int32 id for a categorical one), its leaf id and its bag weight, and to
write its new leaf id.  In hist mode it also writes one (bucket x stat)
table per numeric candidate column of each splittable leaf, and in
either mode one stat vector per category present among a splittable
leaf's in-bag rows, for each of its categorical candidates.  Out-of-bag
rows, closed leaves and non-candidate columns need not be touched, so
the count is a lower bound: the level's time at the chips' HBM
bandwidth is the least time it could take.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

import reference

ROW_BYTES = 12          # leaf id read + written, bag weight read
CAT_ID_BYTES = 4        # int32 category id


class Level(NamedTuple):
    """What one level step of one tree has to touch."""
    rows: int           # in-bag rows of the splittable leaves
    num_reads: int      # sum over those rows of their leaf's numeric candidates
    cat_reads: int      # ... of their leaf's categorical candidates
    num_tables: int     # sum over splittable leaves of numeric candidates
    cat_entries: int    # sum over leaves and categorical candidates of
    #                     the categories present among the in-bag rows


def value_bytes(mode: str, num_bins: int) -> int:
    """Bytes of one row's value of one numeric column as the level reads
    it."""
    if mode == "exact":
        return 4                                   # float32
    return 1 if num_bins <= 256 else 2             # bucket id


def level_bytes(levels, *, mode, num_bins, num_classes) -> int:
    """Least bytes of the level steps of one tree (`levels` as
    `tree_levels` gives them)."""
    total = 0
    for lv in levels:
        total += (lv.num_reads * value_bytes(mode, num_bins)
                  + lv.cat_reads * CAT_ID_BYTES + lv.rows * ROW_BYTES
                  + lv.cat_entries * num_classes * 4)
        if mode == "hist":
            total += lv.num_tables * num_bins * num_classes * 4
    return int(total)


def tree_levels(tree, data, seed, tree_idx, max_depth, min_records=1.0):
    """The `Level` of each depth of a trained tree that a level step ran
    for: rows routed by the tree's own conditions, candidates drawn by
    `reference.candidates`."""
    t = reference.tree_arrays(tree, data.m_num)
    inbag = reference.bag_weights(seed, tree_idx, data.n) > 0
    k = reference.m_prime(data.m)
    out = []
    for depth, nodes, node_of in reference.walk(t, data, max_depth - 1):
        splittable = t["n_node"][nodes] >= 2 * min_records
        if not splittable.any():
            break
        index = np.full(int(node_of.max()) + 1, -1)
        index[nodes[splittable]] = np.arange(int(splittable.sum()))
        rows = np.flatnonzero(inbag & (index[node_of] >= 0))
        leaf = index[node_of[rows]]
        per_leaf = np.bincount(leaf, minlength=int(splittable.sum()))
        cand = reference.candidates(seed, tree_idx, depth, len(nodes),
                                    data.m, k)[splittable]
        n_num = cand[:, :data.m_num].sum(1)
        n_cat = cand[:, data.m_num:].sum(1)
        entries = 0
        for j in range(data.m - data.m_num):
            has = cand[leaf, data.m_num + j]
            pairs = leaf[has] * data.arities[j] + data.cat[rows[has], j]
            entries += len(np.unique(pairs))
        out.append(Level(rows=len(rows), num_reads=int(per_leaf @ n_num),
                         cat_reads=int(per_leaf @ n_cat),
                         num_tables=int(n_num.sum()), cat_entries=entries))
    return out
