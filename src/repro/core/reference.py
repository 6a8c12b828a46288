"""The reference (pre-fusion) tree builder — executable specification.

Kept as the executable specification of Alg. 2: one jitted call per level
piece with numpy round-trips between them, exactly the seed
implementation.  The fused `tree.build_tree` (and the batched
`tree.build_forest`) must reproduce its trees bit-for-bit
(tests/test_fused_level.py, tests/test_forest_batch.py), and
benchmarks/level_step_bench.py measures the fused speedup against it.
EXACT mode only: the histogram mode is an approximation with no
midpoint-exhaustive specification to match (its tests compare the batched
builder against the per-tree fused builder instead).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bagging, class_list, splits
from repro.core.level.engines import (_categorical_supersplits,
                                      _numeric_supersplits)
from repro.core.level.plan import _leaf_totals, _pad_leaves
from repro.core.tree import (LevelStats, Tree, _assemble_tree, _NodeAccum,
                             _tree_setup)


def _eval_conditions_core(num, cat, leaf_of, feat_of_leaf, thr_of_leaf,
                          iscat_of_leaf, mask_of_leaf, m_num):
    from repro.core.level.plan import _eval_conditions_core as impl
    return impl(num.T, cat, leaf_of, feat_of_leaf, thr_of_leaf, iscat_of_leaf,
                mask_of_leaf, m_num)


_evaluate_conditions = functools.partial(jax.jit, static_argnames=("m_num",))(
    _eval_conditions_core)


@jax.jit
def _reassign(leaf_of, bits, new_left, new_right):
    """Alg. 2 step 6: map samples to child leaf ids (0 if child closed)."""
    child = jnp.where(bits, new_left[leaf_of], new_right[leaf_of])
    return jnp.where(leaf_of > 0, child, 0)


def build_tree_reference(
    *,
    num: jnp.ndarray, cat: jnp.ndarray, labels: jnp.ndarray,
    sorted_vals: jnp.ndarray, sorted_idx: jnp.ndarray,
    arities: tuple[int, ...], num_classes: int,
    params, seed: int, tree_idx: int,
    collect_stats: bool = False,
    supersplit_fn=None,
) -> tuple[Tree, list[LevelStats]]:
    """The seed builder: one jitted call per level piece, numpy in between."""
    assert params.split_mode == "exact", \
        "build_tree_reference is the exact-mode specification"
    n, m_num, m_cat, m, max_arity, m_prime = _tree_setup(
        sorted_vals, arities, labels, params)
    task = params.task

    w = bagging.bag_counts(seed, tree_idx, n, params.bagging)
    stats = splits.row_stats(labels, w, num_classes, task)
    cnt = splits.count_fn(task)
    fkey = jax.random.fold_in(jax.random.PRNGKey(seed ^ 0x5EED), tree_idx)

    acc = _NodeAccum(num_classes, task)
    root = acc.new_node(0)
    open_nodes = [root]                       # leaf id h (1-based) -> node id
    leaf_of = jnp.ones((n,), jnp.int32)       # all samples at the root
    stats_log: list[LevelStats] = []

    for depth in range(params.max_depth + 1):
        L = len(open_nodes)
        if L == 0:
            break
        Lp = _pad_leaves(L, params.leaf_pad)

        # leaf totals -> node values & forced closes
        totals = np.asarray(_leaf_totals(leaf_of, stats, w, Lp))  # (Lp+1, S)
        counts = np.asarray(cnt(jnp.asarray(totals)))
        for h, node in enumerate(open_nodes, start=1):
            acc.set_value(node, totals[h], counts[h], task)

        at_max_depth = depth >= params.max_depth
        splittable = np.array(
            [counts[h] >= 2 * params.min_records and not at_max_depth
             for h in range(1, L + 1)] + [False] * (Lp - L))
        if not splittable.any():
            break

        # Alg. 2 step 3: query the splitters for the optimal supersplit
        cand = bagging.candidate_features(fkey, depth, Lp, m, m_prime, params.usb)
        cand = cand & jnp.asarray(splittable)[:, None]
        cand_p = jnp.concatenate([jnp.zeros((1, m), bool), cand], 0)  # leaf 0 = closed

        all_gains = np.full((m, Lp + 1), -np.inf, np.float32)
        all_thr = np.zeros((m, Lp + 1), np.float32)
        all_masks = None
        if m_num:
            if supersplit_fn is not None:
                g, t = supersplit_fn(
                    sorted_vals, sorted_idx, leaf_of, w, stats,
                    cand_p[:, :m_num].T, Lp, params.impurity, task,
                    params.min_records)
            elif params.backend == "kernel":
                from repro.kernels import ops as kops
                g, t = kops.split_scan_supersplit(
                    sorted_vals, sorted_idx, leaf_of, w, labels,
                    cand_p[:, :m_num].T, Lp, params.impurity, task,
                    params.min_records, num_classes=num_classes)
            else:
                g, t = _numeric_supersplits(
                    params.backend, sorted_vals, sorted_idx, leaf_of, w, stats,
                    cand_p[:, :m_num].T, Lp, params.impurity, task,
                    params.min_records)
            all_gains[:m_num], all_thr[:m_num] = np.asarray(g), np.asarray(t)
        if m_cat:
            g, masks = _categorical_supersplits(
                cat.T, leaf_of, w, stats, cand_p[:, m_num:].T, Lp, max_arity,
                params.impurity, task, params.min_records)
            all_gains[m_num:] = np.asarray(g)
            all_masks = np.asarray(masks)                    # (m_cat, Lp+1, V)

        # tree builder merges partial supersplits (Alg. 2 step 3, final argmax)
        best_feat = all_gains.argmax(axis=0)                 # (Lp+1,)
        best_gain = all_gains[best_feat, np.arange(Lp + 1)]

        # Alg. 2 step 8: close leaves with no good condition
        feat_of_leaf = np.zeros(Lp + 1, np.int32)
        thr_of_leaf = np.zeros(Lp + 1, np.float32)
        iscat_of_leaf = np.zeros(Lp + 1, bool)
        mask_of_leaf = np.zeros((Lp + 1, max_arity), bool)
        new_left = np.zeros(Lp + 1, np.int32)
        new_right = np.zeros(Lp + 1, np.int32)
        next_open: list[int] = []
        any_split = False
        for h in range(1, L + 1):
            node = open_nodes[h - 1]
            if not splittable[h - 1] or not np.isfinite(best_gain[h]) or best_gain[h] <= 1e-9:
                continue
            j = int(best_feat[h])
            any_split = True
            acc.feature[node] = j
            acc.gain[node] = float(best_gain[h])
            feat_of_leaf[h] = j
            if j < m_num:
                acc.threshold[node] = float(all_thr[j, h])
                thr_of_leaf[h] = all_thr[j, h]
            else:
                acc.is_cat[node] = True
                iscat_of_leaf[h] = True
                cm = all_masks[j - m_num, h]
                acc.cat_mask[node] = cm.copy()
                mask_of_leaf[h] = cm
            lc, rc = acc.new_node(depth + 1), acc.new_node(depth + 1)
            acc.children[node] = [lc, rc]
            next_open.extend([lc, rc])
            new_left[h] = len(next_open) - 1               # 1-based ids below
            new_right[h] = len(next_open)

        if collect_stats:
            open_w = float(counts[1:L + 1].sum())
            stats_log.append(LevelStats(
                depth=depth, open_leaves=L,
                network_bits_bitmap=int(open_w),
                network_bits_supersplit=int(m * (Lp + 1) * 64),
                class_list_bits=class_list.storage_bits(n, L),
                feature_passes=int(min(m_prime * (1 if params.usb else L), m)),
                rows_scanned=n * min(m_prime * (1 if params.usb else L), m)))

        if not any_split:
            break

        # Alg. 2 steps 5-7: evaluate conditions (1 bit/sample) and reassign
        bits = _evaluate_conditions(
            num, cat, leaf_of, jnp.asarray(feat_of_leaf), jnp.asarray(thr_of_leaf),
            jnp.asarray(iscat_of_leaf), jnp.asarray(mask_of_leaf), m_num)
        leaf_of = _reassign(leaf_of, bits, jnp.asarray(new_left), jnp.asarray(new_right))
        open_nodes = next_open

        # Sprint-style pruning switch (paper §3): compact rows in closed
        # leaves once they dominate.  The presorted order is FILTERED, not
        # re-sorted (stability preserves it), so the one-time cost is one
        # pass — the trade-off rule the paper describes.
        if params.prune_closed_frac < 1.0 and n > 0:
            lf_np = np.asarray(leaf_of)
            keep = lf_np > 0
            frac_closed = 1.0 - keep.mean()
            if frac_closed >= params.prune_closed_frac and keep.any() \
                    and keep.sum() < n:
                remap = np.cumsum(keep) - 1
                idx_np = np.asarray(sorted_idx)
                vals_np = np.asarray(sorted_vals)
                kept_cols = keep[idx_np]                      # (m_num, n)
                n_new = int(keep.sum())
                new_idx = np.empty((m_num, n_new), np.int32)
                new_vals = np.empty((m_num, n_new), np.float32)
                for j in range(m_num):
                    sel = kept_cols[j]
                    new_idx[j] = remap[idx_np[j][sel]]
                    new_vals[j] = vals_np[j][sel]
                sorted_idx = jnp.asarray(new_idx)
                sorted_vals = jnp.asarray(new_vals)
                num = num[jnp.asarray(keep)] if num.size else num
                cat = cat[jnp.asarray(keep)] if cat.size else cat
                stats = stats[jnp.asarray(keep)]
                w = w[jnp.asarray(keep)]
                labels = labels[jnp.asarray(keep)]
                leaf_of = jnp.asarray(lf_np[keep])
                n = n_new

    return _assemble_tree(acc, max_arity, m_num, task), stats_log
