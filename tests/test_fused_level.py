"""Fused level step (tree.build_tree) vs the reference builder, the Pallas
categorical path, and the stacked single-call forest predictor."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import forest as forest_lib
from repro.core import presort, splits, tree as tree_lib
from repro.core.dataset import from_numpy
from repro.core.forest import RandomForest
from repro.kernels import feat_hist, ops as kops


def _build_both(ds, params, seed=5, tree_idx=0, supersplit_fn=None):
    if ds.m_num:
        si = presort.presort_columns(ds.num)
        sv = presort.gather_sorted(ds.num, si)
    else:
        si = jnp.zeros((0, ds.n), jnp.int32)
        sv = jnp.zeros((0, ds.n), jnp.float32)
    kw = dict(num=ds.num, cat=ds.cat, labels=ds.labels, sorted_vals=sv,
              sorted_idx=si, arities=ds.arities, num_classes=ds.num_classes,
              params=params, seed=seed, tree_idx=tree_idx,
              supersplit_fn=supersplit_fn)
    fused, _ = tree_lib.build_tree(**kw)
    ref, _ = tree_lib.build_tree_reference(**kw)
    return fused, ref


def _assert_identical(ta, tb):
    """Bit-identical flat trees: splits, thresholds, masks, leaf values."""
    assert ta.num_nodes == tb.num_nodes
    np.testing.assert_array_equal(ta.feature, tb.feature)
    np.testing.assert_array_equal(ta.children, tb.children)
    np.testing.assert_array_equal(ta.threshold, tb.threshold)
    np.testing.assert_array_equal(ta.is_cat, tb.is_cat)
    np.testing.assert_array_equal(ta.cat_mask, tb.cat_mask)
    np.testing.assert_array_equal(ta.value, tb.value)
    np.testing.assert_array_equal(ta.n_node, tb.n_node)
    np.testing.assert_array_equal(ta.gain, tb.gain)
    np.testing.assert_array_equal(ta.depth, tb.depth)


@pytest.fixture(scope="module")
def mixed_ds():
    rng = np.random.default_rng(3)
    n = 1100
    num = rng.normal(size=(n, 4)).astype(np.float32)
    cat = rng.integers(0, 5, size=(n, 2)).astype(np.int32)
    y = ((num[:, 0] > 0) ^ (cat[:, 0] >= 3)).astype(np.int32)
    return from_numpy(num, cat, y)


@pytest.mark.parametrize("backend", ["segment", "scan", "kernel"])
def test_fused_matches_reference_classification_mixed(mixed_ds, backend):
    p = tree_lib.TreeParams(max_depth=4, backend=backend)
    _assert_identical(*_build_both(mixed_ds, p))


@pytest.mark.parametrize("backend", ["segment", "scan"])
def test_fused_matches_reference_regression(backend):
    rng = np.random.default_rng(1)
    n = 900
    num = rng.normal(size=(n, 4)).astype(np.float32)
    y = (2 * num[:, 0] + num[:, 1] ** 2
         + 0.1 * rng.normal(size=n)).astype(np.float32)
    ds = from_numpy(num, None, y, task="regression")
    p = tree_lib.TreeParams(max_depth=5, backend=backend,
                            impurity="variance", task="regression",
                            bagging="none")
    _assert_identical(*_build_both(ds, p, seed=2))


def test_fused_matches_reference_pure_categorical():
    rng = np.random.default_rng(0)
    n = 700
    cat = rng.integers(0, 6, size=(n, 3)).astype(np.int32)
    y = ((cat[:, 0] % 2) ^ (cat[:, 1] >= 3)).astype(np.int32)
    ds = from_numpy(None, cat, y)
    p = tree_lib.TreeParams(max_depth=4)
    _assert_identical(*_build_both(ds, p))


def test_fused_matches_reference_deeper_multiclass():
    """More levels (several leaf paddings) + 3 classes + entropy."""
    rng = np.random.default_rng(7)
    n = 2000
    num = rng.normal(size=(n, 5)).astype(np.float32)
    y = (np.digitize(num[:, 0] + num[:, 1], [-0.6, 0.6])).astype(np.int32)
    ds = from_numpy(num, None, y)
    p = tree_lib.TreeParams(max_depth=7, min_records=2, impurity="entropy")
    _assert_identical(*_build_both(ds, p, seed=9))


# ---------------------------------------------------------------------------
# Pallas table-kernel categorical supersplit vs the jnp reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("V,bv", [
    (6, 4),        # arity NOT a multiple of bv -> padded category blocks
    (16, 4),       # exact multiple
    (37, 8),       # high-ish arity, non-multiple
    (130, 32),     # > one lane group, non-multiple
])
def test_kernel_categorical_path_matches_reference(V, bv):
    rng = np.random.default_rng(V)
    n, m, L, C = 640, 3, 4, 3
    x = rng.integers(0, V, size=(n, m)).astype(np.int32)
    leaf = rng.integers(0, L + 1, n).astype(np.int32)
    w = rng.integers(0, 3, n).astype(np.float32)
    y = rng.integers(0, C, n).astype(np.int32)
    stats = splits.row_stats(jnp.asarray(y), jnp.asarray(w), C,
                             "classification")
    cand = np.ones((m, L + 1), bool)
    cand[:, 0] = False

    tables = kops.categorical_tables(
        jnp.asarray(x.T), jnp.asarray(leaf), jnp.asarray(w),
        jnp.asarray(y), V=V, Lp=L, num_classes=C)
    assert tables.shape == (m, L + 1, C, V)
    # the same tables with the value axis tiled in bv-sized blocks (and 2
    # leaf slots per block), so a padded last value block is exercised
    tiled = feat_hist.feat_hist_pallas(
        jnp.asarray(x.T), jnp.asarray(leaf), jnp.asarray(w),
        jnp.asarray(y.astype(np.float32)), W=L + 1, V=V, s_dim=C, bn=128,
        task="classification", interpret=True, plan=(2, bv))
    np.testing.assert_array_equal(np.asarray(tiled), np.asarray(tables))
    for j in range(m):
        g_k, m_k = splits.best_categorical_split_from_table(
            tables[j], jnp.asarray(cand[j]))
        g_r, m_r = splits.best_categorical_split(
            jnp.asarray(x[:, j]), jnp.asarray(leaf), jnp.asarray(w), stats,
            jnp.asarray(cand[j]), L, V)
        fin = np.isfinite(np.asarray(g_r))
        assert (np.isfinite(np.asarray(g_k)) == fin).all()
        np.testing.assert_allclose(np.asarray(g_k)[fin],
                                   np.asarray(g_r)[fin], atol=1e-4, rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(m_k)[fin],
                                      np.asarray(m_r)[fin])


def test_fused_kernel_backend_with_high_arity_categoricals():
    """End-to-end: fused builder, kernel backend, arity not a bv multiple."""
    rng = np.random.default_rng(4)
    n = 600
    num = rng.normal(size=(n, 2)).astype(np.float32)
    cat = np.stack([rng.integers(0, 7, n), rng.integers(0, 13, n)], 1).astype(np.int32)
    y = ((num[:, 0] > 0) ^ (cat[:, 1] >= 6)).astype(np.int32)
    ds = from_numpy(num, cat, y)
    p = tree_lib.TreeParams(max_depth=3, backend="kernel")
    _assert_identical(*_build_both(ds, p))


# ---------------------------------------------------------------------------
# Stacked forest inference: one jitted call, no per-tree retrace
# ---------------------------------------------------------------------------

def test_predict_proba_single_jitted_call_100_trees(mixed_ds):
    rf = RandomForest(tree_lib.TreeParams(max_depth=3), num_trees=100,
                      seed=0).fit(mixed_ds)
    assert rf.packed is not None and rf.packed.num_trees == 100

    calls = []
    orig = forest_lib._forest_predict

    def counting(*args, **kwargs):
        calls.append(1)
        return orig(*args, **kwargs)

    forest_lib._forest_predict = counting
    try:
        # the per-tree path must be gone entirely
        def boom(*a, **k):
            raise AssertionError("per-tree _predict_jit used by predict_proba")
        saved = tree_lib._predict_jit
        tree_lib._predict_jit = boom
        try:
            traces0 = obs.counter("predict.traces")
            p1 = rf.predict_proba(mixed_ds.num, mixed_ds.cat)
            assert len(calls) == 1                    # exactly one jitted call
            assert obs.counter("predict.traces") - traces0 <= 1  # one trace
            p2 = rf.predict_proba(mixed_ds.num, mixed_ds.cat)
            assert len(calls) == 2
            assert obs.counter("predict.traces") - traces0 <= 1  # no retrace
        finally:
            tree_lib._predict_jit = saved
    finally:
        forest_lib._forest_predict = orig

    # parity with the per-tree evaluator
    acc = None
    for tr in rf.trees:
        p = np.asarray(tr.predict_raw(mixed_ds.num, mixed_ds.cat))
        acc = p if acc is None else acc + p
    np.testing.assert_allclose(np.asarray(p1), acc / len(rf.trees), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))


def test_predict_proba_up_to_prefix(mixed_ds):
    rf = RandomForest(tree_lib.TreeParams(max_depth=3), num_trees=6,
                      seed=1).fit(mixed_ds)
    p3 = np.asarray(rf.predict_proba(mixed_ds.num, mixed_ds.cat, up_to=3))
    acc = None
    for tr in rf.trees[:3]:
        p = np.asarray(tr.predict_raw(mixed_ds.num, mixed_ds.cat))
        acc = p if acc is None else acc + p
    np.testing.assert_allclose(p3, acc / 3, atol=1e-6)
