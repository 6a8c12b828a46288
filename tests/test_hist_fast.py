"""Histogram fast path: bin cache, fused table build, subtraction.

Contracts under test (ISSUE 5):
  * the bin cache is BIT-PACKED (uint8 up to 256 buckets, uint16 past)
    and num_bins=256 does not overflow/wrap the uint8 ids;
  * `splits.feature_count_tables` (one flat scatter for all columns) and
    the Pallas `feat_hist` kernel build identical tables, equal to the
    old per-column `categorical_count_table` path;
  * subtraction (child = parent − sibling) is BIT-IDENTICAL to a plain
    per-level table rebuild — node for node, batched and per-tree, with
    `prune_closed_frac` on (pruning renumbers rows, not leaves, so the
    carried tables survive);
  * the fast path keeps one batched level program per depth (dispatch-
    and trace-counted), and regression (GBT) forces the plain rebuild;
  * pre-quantized bucket state that disagrees with TreeParams raises at
    fit time instead of being silently ignored.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import presort, splits, tree as tree_lib
from repro.core.dataset import from_numpy
from repro.core.forest import RandomForest
from repro.data.synthetic import make_tabular


def _assert_identical(ta, tb, ctx=""):
    assert ta.num_nodes == tb.num_nodes, ctx
    for name in ("feature", "children", "threshold", "is_cat", "cat_mask",
                 "value", "n_node", "gain", "depth"):
        np.testing.assert_array_equal(getattr(ta, name), getattr(tb, name),
                                      err_msg=f"{ctx}:{name}")


@pytest.fixture(scope="module")
def skewed_ds():
    rng = np.random.default_rng(0)
    n = 2048
    num = rng.normal(size=(n, 5)).astype(np.float32)
    y = ((num[:, 0] > 1.0) | (num[:, 1] * num[:, 2] > 1.5)).astype(np.int32)
    return from_numpy(num, None, y)


# ---------------------------------------------------------------------------
# Bit-packed bin cache
# ---------------------------------------------------------------------------

def test_bin_cache_dtype_packing():
    assert presort.bin_dtype(16) == jnp.uint8
    assert presort.bin_dtype(255) == jnp.uint8
    assert presort.bin_dtype(256) == jnp.uint8
    assert presort.bin_dtype(257) == jnp.uint16
    assert presort.bin_dtype(4096) == jnp.uint16


@pytest.mark.parametrize("B", [255, 256, 300])
def test_bin_cache_no_overflow_at_high_bin_ids(B):
    """num_bins=256 is the uint8 edge: ids up to 255 must survive the
    packed dtype un-wrapped (and 300 bins must pick uint16)."""
    rng = np.random.default_rng(1)
    n = 4096
    num = rng.permutation(n).astype(np.float32)[:, None]  # n distinct values
    si = presort.presort_columns(jnp.asarray(num))
    sv = presort.gather_sorted(jnp.asarray(num), si)
    bins, edges = presort.quantize(jnp.asarray(num), sv, B)
    assert bins.dtype == presort.bin_dtype(B)
    b = np.asarray(bins)[0]
    assert b.min() == 0 and int(b.max()) == B - 1       # top bucket reached
    # packed ids agree with an unpacked int32 searchsorted reference
    ref = np.searchsorted(np.asarray(edges)[0, :-1], num[:, 0], side="left")
    np.testing.assert_array_equal(b.astype(np.int64), ref)
    # the partition rule survives the packing at every cut incl. 254/255
    for cut in (0, B // 2, B - 2):
        np.testing.assert_array_equal(
            b <= cut, num[:, 0] <= np.asarray(edges)[0, cut])


def test_hist_forest_at_256_bins_trains_and_uses_edges(skewed_ds):
    """End-to-end uint8 guard: a 256-bin fit must produce edge thresholds
    and match its own hist_subtract=False rebuild bit-for-bit."""
    p = tree_lib.TreeParams(max_depth=4, split_mode="hist", num_bins=256)
    rf = RandomForest(p, num_trees=2, seed=2).fit(skewed_ds)
    rf2 = RandomForest(dataclasses.replace(p, hist_subtract=False),
                       num_trees=2, seed=2).fit(skewed_ds)
    edges = np.asarray(skewed_ds.quantize(256)[1])
    checked = 0
    for ta, tb in zip(rf.trees, rf2.trees):
        _assert_identical(ta, tb, "256-bins")
        for i in range(ta.num_nodes):
            j = ta.feature[i]
            if j >= 0:
                assert ta.threshold[i] in edges[j]
                checked += 1
    assert checked > 0


# ---------------------------------------------------------------------------
# Fused multi-feature table build
# ---------------------------------------------------------------------------

def test_feature_tables_match_per_column_and_kernel():
    rng = np.random.default_rng(2)
    n, m, L, B, C = 900, 6, 5, 33, 3
    bins = rng.integers(0, B, size=(m, n)).astype(np.uint8)
    leaf = rng.integers(0, L + 1, n).astype(np.int32)
    w = rng.integers(0, 3, n).astype(np.float32)
    y = rng.integers(0, C, n).astype(np.int32)
    stats = splits.row_stats(jnp.asarray(y), jnp.asarray(w), C,
                             "classification")
    fused = splits.feature_count_tables(
        jnp.asarray(bins), jnp.asarray(leaf), jnp.asarray(w), stats, L, B)
    per_col = jnp.stack([
        splits.categorical_count_table(
            jnp.asarray(bins[j].astype(np.int32)), jnp.asarray(leaf),
            jnp.asarray(w), stats, L, B) for j in range(m)])
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(per_col))

    from repro.kernels import ops as kops
    kern = kops.feature_tables(
        jnp.asarray(bins), jnp.asarray(leaf), jnp.asarray(w),
        jnp.asarray(y), B=B, W=L + 1, num_classes=C)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(kern))


def test_feature_tables_discard_slot_rows_do_not_leak():
    """Rows mapped to slot 0 (the subtraction path's derive rows) must
    leave every real slot untouched and slot 0 all-zero."""
    rng = np.random.default_rng(3)
    n, m, L, B = 400, 3, 4, 9
    bins = rng.integers(0, B, size=(m, n)).astype(np.uint8)
    slots = rng.integers(0, L + 1, n).astype(np.int32)
    w = np.ones(n, np.float32)
    stats = jnp.ones((n, 2), jnp.float32)
    full = splits.feature_count_tables(
        jnp.asarray(bins), jnp.asarray(slots), jnp.asarray(w), stats, L, B)
    assert np.asarray(full)[:, 0].sum() == 0                 # slot 0 empty
    # zeroing a slot's rows changes only that slot
    slots2 = np.where(slots == 2, 0, slots)
    part = splits.feature_count_tables(
        jnp.asarray(bins), jnp.asarray(slots2), jnp.asarray(w), stats, L, B)
    np.testing.assert_array_equal(np.asarray(part)[:, 1],
                                  np.asarray(full)[:, 1])
    np.testing.assert_array_equal(np.asarray(part)[:, 3:],
                                  np.asarray(full)[:, 3:])
    assert np.asarray(part)[:, 2].sum() == 0


# ---------------------------------------------------------------------------
# Subtraction vs plain rebuild (the tentpole bit-parity contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["segment", "kernel"])
@pytest.mark.parametrize("tree_batch", [1, 4])
def test_subtraction_bit_identical_to_plain(skewed_ds, backend, tree_batch):
    p = tree_lib.TreeParams(max_depth=6, min_records=20, backend=backend,
                            split_mode="hist", num_bins=32)
    sub = RandomForest(p, num_trees=4, seed=9,
                       tree_batch=tree_batch).fit(skewed_ds)
    plain = RandomForest(dataclasses.replace(p, hist_subtract=False),
                         num_trees=4, seed=9,
                         tree_batch=tree_batch).fit(skewed_ds)
    assert max(t.max_depth_reached for t in sub.trees) >= 3
    for t, (ta, tb) in enumerate(zip(sub.trees, plain.trees)):
        _assert_identical(ta, tb, f"{backend}/tb{tree_batch}/tree{t}")


@pytest.mark.parametrize("tree_batch", [1, 3])
def test_subtraction_survives_pruning(skewed_ds, tree_batch):
    """prune_closed_frac renumbers ROWS, not leaves: the carried tables
    stay valid and the pruned fit equals the unpruned one node-for-node
    (both with subtraction on, and each equal to the plain rebuild) —
    through the per-tree driver and the batched one."""
    p = tree_lib.TreeParams(max_depth=8, min_records=30, split_mode="hist",
                            num_bins=32)
    base = RandomForest(p, num_trees=3, seed=4,
                        tree_batch=tree_batch).fit(skewed_ds)
    pruned = RandomForest(dataclasses.replace(p, prune_closed_frac=0.25),
                          num_trees=3, seed=4,
                          tree_batch=tree_batch).fit(skewed_ds)
    plain_pruned = RandomForest(
        dataclasses.replace(p, prune_closed_frac=0.25, hist_subtract=False),
        num_trees=3, seed=4, tree_batch=tree_batch).fit(skewed_ds)
    for ta, tb, tc in zip(base.trees, pruned.trees, plain_pruned.trees):
        _assert_identical(ta, tb, f"tb{tree_batch}:pruned-vs-base")
        _assert_identical(tb, tc, f"tb{tree_batch}:sub-vs-plain")


def test_fast_path_one_level_program_per_depth(skewed_ds):
    """Subtraction keeps the one-batched-program-per-depth shape and never
    falls back to per-tree dispatches; warm refits do not retrace."""
    p = tree_lib.TreeParams(max_depth=5, split_mode="hist", num_bins=16)
    rf = RandomForest(p, num_trees=4, seed=0, tree_batch=4).fit(skewed_ds)
    calls0 = obs.counter("level.dispatches")
    steps0 = obs.counter("level.tree_dispatches")
    traces0 = obs.counter("level.traces")
    rf2 = RandomForest(p, num_trees=4, seed=0, tree_batch=4).fit(skewed_ds)
    calls = obs.counter("level.dispatches") - calls0
    D = max(t.max_depth_reached for t in rf2.trees)
    assert D <= calls <= p.max_depth + 1, (calls, D)
    assert obs.counter("level.tree_dispatches") == steps0
    assert obs.counter("level.traces") == traces0
    for ta, tb in zip(rf.trees, rf2.trees):
        _assert_identical(ta, tb, "warm-vs-cold")


def test_regression_forces_plain_rebuild():
    """Float regression tables cannot subtract exactly — the plan must
    rebuild plain (carries_tables False) while classification carries."""
    from repro.core.level.plan import make_plan
    ph = tree_lib.TreeParams(split_mode="hist", num_bins=16)
    plan_c = make_plan(ph, m_num=3, m_cat=0, max_arity=1, num_classes=2,
                       m_prime=2)
    assert plan_c.carries_tables and plan_c.use_bin_cuts
    pr = dataclasses.replace(ph, task="regression", impurity="variance")
    plan_r = make_plan(pr, m_num=3, m_cat=0, max_arity=1, num_classes=2,
                       m_prime=2)
    assert plan_r.use_bin_cuts and not plan_r.carries_tables
    po = dataclasses.replace(ph, hist_subtract=False)
    assert not make_plan(po, m_num=3, m_cat=0, max_arity=1, num_classes=2,
                         m_prime=2).carries_tables


# ---------------------------------------------------------------------------
# Fit-time validation of pre-quantized bucket state
# ---------------------------------------------------------------------------

def test_prequantized_num_bins_mismatch_raises(skewed_ds):
    bin_of, edges = skewed_ds.quantize(32)
    kw = dict(num=skewed_ds.num, cat=skewed_ds.cat, labels=skewed_ds.labels,
              sorted_vals=presort.gather_sorted(
                  skewed_ds.num, presort.presort_columns(skewed_ds.num)),
              sorted_idx=presort.presort_columns(skewed_ds.num),
              arities=skewed_ds.arities, num_classes=skewed_ds.num_classes,
              seed=0)
    p_bad = tree_lib.TreeParams(split_mode="hist", num_bins=64)
    with pytest.raises(ValueError, match="num_bins"):
        tree_lib.build_tree(params=p_bad, tree_idx=0, bin_of=bin_of,
                            bin_edges=edges, **kw)
    with pytest.raises(ValueError, match="num_bins"):
        tree_lib.build_forest(params=p_bad, tree_indices=range(2),
                              bin_of=bin_of, bin_edges=edges, **kw)
    # matching state passes (and equals the self-quantized fit)
    p_ok = tree_lib.TreeParams(split_mode="hist", num_bins=32, max_depth=3)
    ta, _ = tree_lib.build_tree(params=p_ok, tree_idx=0, bin_of=bin_of,
                                bin_edges=edges, **kw)
    tb, _ = tree_lib.build_tree(params=p_ok, tree_idx=0, **kw)
    _assert_identical(ta, tb, "prequantized-vs-self")
    # a bin cache too narrow for the bucket budget is rejected
    with pytest.raises(ValueError, match="dtype"):
        tree_lib.build_tree(
            params=tree_lib.TreeParams(split_mode="hist", num_bins=300),
            tree_idx=0,
            bin_of=jnp.zeros(bin_of.shape, jnp.uint8),
            bin_edges=jnp.zeros((bin_of.shape[0], 300), jnp.float32), **kw)
