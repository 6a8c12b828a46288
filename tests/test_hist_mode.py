"""Histogram (PLANET-style) approximate split mode on the fused plumbing.

Contracts under test:
  * the bucket scorer (`splits.best_numeric_split_histogram`) matches a
    numpy brute-force over the same count table, and equals the EXACT
    search when every distinct value gets its own bucket;
  * hist thresholds are bucket edges, so training-time bucket partitions
    and inference-time `x <= thr` partitions agree exactly;
  * `tree.build_forest` under `split_mode="hist"` is bit-identical per
    tree to the per-tree fused builder — including uneven finish depths
    (early-finish masking) — and issues ONE batched level program per
    depth (mirrors tests/test_forest_batch.py for exact mode);
  * `split_mode="exact"` is the default and stays on the exact engines
    (tests/test_fused_level.py pins its bit-parity with the reference).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import presort, splits, tree as tree_lib
from repro.core.dataset import from_numpy
from repro.core.forest import RandomForest
from repro.core.gbt import GBTModel, GBTParams
from repro.data.synthetic import make_tabular, train_test_split


def _build_kw(ds, seed=5):
    if ds.m_num:
        si = presort.presort_columns(ds.num)
        sv = presort.gather_sorted(ds.num, si)
    else:
        sv = jnp.zeros((0, ds.n), jnp.float32)
        si = jnp.zeros((0, ds.n), jnp.int32)
    return dict(num=ds.num, cat=ds.cat, labels=ds.labels, sorted_vals=sv,
                sorted_idx=si, arities=ds.arities,
                num_classes=ds.num_classes, seed=seed)


def _assert_identical(ta, tb, ctx=""):
    assert ta.num_nodes == tb.num_nodes, ctx
    for name in ("feature", "children", "threshold", "is_cat", "cat_mask",
                 "value", "n_node", "gain", "depth"):
        np.testing.assert_array_equal(getattr(ta, name), getattr(tb, name),
                                      err_msg=f"{ctx}:{name}")


@pytest.fixture(scope="module")
def mixed_ds():
    rng = np.random.default_rng(3)
    n = 1100
    num = rng.normal(size=(n, 4)).astype(np.float32)
    cat = rng.integers(0, 5, size=(n, 2)).astype(np.int32)
    y = ((num[:, 0] > 0) ^ (cat[:, 0] >= 3)).astype(np.int32)
    return from_numpy(num, cat, y)


# ---------------------------------------------------------------------------
# The bucket scorer vs numpy
# ---------------------------------------------------------------------------

def _np_imp_gini(h):
    n = h.sum(-1)
    return n - np.divide((h * h).sum(-1), n, out=np.zeros_like(n),
                         where=n > 0)


def test_hist_scorer_matches_numpy_bruteforce():
    rng = np.random.default_rng(0)
    L, B, C = 3, 12, 3
    table = rng.integers(0, 7, size=(L + 1, B, C)).astype(np.float32)
    table[1, :, 1:] = 0.0                         # single-class leaf
    table[2] = 0.0                                # empty leaf
    edges = np.sort(rng.normal(size=B)).astype(np.float32)
    cand = np.array([False] + [True] * L)
    g, t = splits.best_numeric_split_histogram(    # scorer layout (L+1, S, B)
        jnp.asarray(table.transpose(0, 2, 1)), jnp.asarray(cand))
    g, t = np.asarray(g), np.asarray(t)
    tb = table.astype(np.float64)
    for h in range(1, L + 1):
        total = tb[h].sum(0)
        best_g, best_b = -np.inf, None
        for b in range(B - 1):
            left = tb[h, :b + 1].sum(0)
            right = total - left
            if left.sum() < 1 or right.sum() < 1:
                continue
            gb = (_np_imp_gini(total) - _np_imp_gini(left)
                  - _np_imp_gini(right))
            if gb > best_g:                       # first max wins
                best_g, best_b = gb, b
        if best_b is None:
            assert not np.isfinite(g[h]), h
            continue
        np.testing.assert_allclose(g[h], best_g, rtol=1e-5, atol=1e-5,
                                   err_msg=f"leaf{h}")
        # the scorer reports the BIN INDEX; the host decodes edges[cut]
        assert edges[int(t[h])] == edges[best_b], f"leaf{h}"


def test_hist_equals_exact_when_bins_cover_every_value():
    """One bucket per row: every boundary between distinct values is an
    edge, so the hist gains must equal the exact search's (thresholds are
    edges instead of midpoints — same partitions, same gains)."""
    rng = np.random.default_rng(4)
    n, L = 200, 3
    num = (np.round(rng.normal(size=(n, 2)) * 3) / 4).astype(np.float32)
    leaf = rng.integers(0, L + 1, n).astype(np.int32)
    w = rng.integers(0, 3, n).astype(np.float32)
    y = rng.integers(0, 2, n).astype(np.int32)
    stats = splits.row_stats(jnp.asarray(y), jnp.asarray(w), 2,
                             "classification")
    cand = np.ones((2, L + 1), bool)
    cand[:, 0] = False
    si = presort.presort_columns(jnp.asarray(num))
    sv = presort.gather_sorted(jnp.asarray(num), si)
    edges = presort.quantize_edges(sv, n)          # every row its own bucket
    bin_of = presort.bin_columns(jnp.asarray(num), edges)
    for j in range(2):
        g_h, cut_h = splits.best_numeric_split_histogram(
            splits.categorical_count_table(
                bin_of[j].astype(jnp.int32), jnp.asarray(leaf),
                jnp.asarray(w), stats, L, n),
            jnp.asarray(cand[j]))
        t_h = jnp.where(jnp.isfinite(g_h),
                        edges[j][cut_h.astype(jnp.int32)], 0.0)
        g_e, _ = splits.best_numeric_split_segment(
            sv[j], jnp.asarray(leaf)[si[j]], jnp.asarray(w)[si[j]],
            stats[si[j]], jnp.asarray(cand[j]), L)
        fin = np.isfinite(np.asarray(g_e))
        assert (np.isfinite(np.asarray(g_h)) == fin).all(), j
        np.testing.assert_allclose(np.asarray(g_h)[fin],
                                   np.asarray(g_e)[fin], rtol=1e-4,
                                   atol=1e-4, err_msg=f"col{j}")
        # hist thresholds must land on actual bucket edges
        for h in np.nonzero(fin)[0]:
            assert np.asarray(t_h)[h] in np.asarray(edges[j]), (j, h)


def test_bucket_partition_consistent_with_threshold_rule():
    """b(x) <= cut  <=>  x <= edges[cut]: the partition scored at training
    time is exactly the partition the tree applies at inference time."""
    rng = np.random.default_rng(8)
    num = np.round(rng.normal(size=(500, 3)) * 2).astype(np.float32) / 2
    si = presort.presort_columns(jnp.asarray(num))
    sv = presort.gather_sorted(jnp.asarray(num), si)
    for B in (2, 7, 32):
        edges = np.asarray(presort.quantize_edges(sv, B))
        bins = np.asarray(presort.bin_columns(jnp.asarray(num), edges))
        assert bins.min() >= 0 and bins.max() < B
        for j in range(3):
            for cut in range(B - 1):
                np.testing.assert_array_equal(
                    bins[j] <= cut, num[:, j] <= edges[j, cut],
                    err_msg=f"B{B}/col{j}/cut{cut}")


# ---------------------------------------------------------------------------
# The fused builders under split_mode="hist"
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["segment", "kernel"])
def test_hist_batched_matches_per_tree(mixed_ds, backend):
    """build_forest(hist) is bit-identical per tree to build_tree(hist),
    with uneven finish depths exercising the early-finish masking
    (satellite of the exact-mode contract in tests/test_forest_batch.py).
    The kernel backend routes the bucket tables through the Pallas
    feat_hist kernel."""
    kw = _build_kw(mixed_ds)
    p = tree_lib.TreeParams(max_depth=5, min_records=60, backend=backend,
                            split_mode="hist", num_bins=8)
    trees, _ = tree_lib.build_forest(params=p, tree_indices=range(4), **kw)
    depths = {t.max_depth_reached for t in trees}
    assert len(depths) > 1, "fixture must exercise uneven finish depths"
    for t in range(4):
        solo, _ = tree_lib.build_tree(params=p, tree_idx=t, **kw)
        _assert_identical(trees[t], solo, f"hist/{backend}/tree{t}")


def test_hist_one_level_program_per_depth(mixed_ds):
    """fit(split_mode='hist') keeps the one-batched-program-per-depth
    property — dispatch- and trace-counted."""
    p = tree_lib.TreeParams(max_depth=4, split_mode="hist", num_bins=32)
    rf = RandomForest(p, num_trees=8, seed=0, tree_batch=8)
    rf.fit(mixed_ds)                                   # warm the jit caches

    calls0 = obs.counter("level.dispatches")
    steps0 = obs.counter("level.tree_dispatches")
    traces0 = obs.counter("level.traces")
    rf2 = RandomForest(p, num_trees=8, seed=0, tree_batch=8).fit(mixed_ds)
    calls = obs.counter("level.dispatches") - calls0
    D = max(t.max_depth_reached for t in rf2.trees)
    assert D <= calls <= p.max_depth + 1, (calls, D)
    # no per-tree fallback; warm: no retrace
    assert obs.counter("level.tree_dispatches") == steps0
    assert obs.counter("level.traces") == traces0
    for ta, tb in zip(rf.trees, rf2.trees):
        _assert_identical(ta, tb, "hist-warm-vs-cold")


def test_hist_thresholds_are_bucket_edges(mixed_ds):
    """Every numeric split a hist tree makes must use a quantizer edge."""
    B = 16
    bin_of, edges = mixed_ds.quantize(B)
    p = tree_lib.TreeParams(max_depth=5, split_mode="hist", num_bins=B)
    rf = RandomForest(p, num_trees=2, seed=1).fit(mixed_ds)
    edges = np.asarray(edges)
    checked = 0
    for tr in rf.trees:
        for i in range(tr.num_nodes):
            j = tr.feature[i]
            if j < 0 or tr.is_cat[i]:
                continue
            assert tr.threshold[i] in edges[j], (i, j)
            checked += 1
    assert checked > 0


def test_hist_close_to_exact_auc(mixed_ds):
    """The approximation-quality contract at test scale; the benchmark
    (benchmarks/run.py hist -> BENCH_hist_mode.json) records the headline
    num_bins=255 delta."""
    ds = make_tabular("majority", 4000, num_informative=4, num_useless=4,
                      seed=7)
    tr, te = train_test_split(ds)
    exact = RandomForest(tree_lib.TreeParams(max_depth=6), num_trees=8,
                         seed=3).fit(tr)
    hist = RandomForest(
        tree_lib.TreeParams(max_depth=6, split_mode="hist", num_bins=64),
        num_trees=8, seed=3).fit(tr)
    assert abs(exact.auc(te) - hist.auc(te)) < 0.02


def test_hist_pure_categorical_unaffected():
    """With no numeric columns hist mode degenerates to the exact builder
    (buckets only approximate numeric splits)."""
    rng = np.random.default_rng(0)
    n = 700
    cat = rng.integers(0, 6, size=(n, 3)).astype(np.int32)
    y = ((cat[:, 0] % 2) ^ (cat[:, 1] >= 3)).astype(np.int32)
    ds = from_numpy(None, cat, y)
    kw = _build_kw(ds)
    pe = tree_lib.TreeParams(max_depth=4)
    ph = tree_lib.TreeParams(max_depth=4, split_mode="hist", num_bins=16)
    te_, _ = tree_lib.build_tree(params=pe, tree_idx=0, **kw)
    th_, _ = tree_lib.build_tree(params=ph, tree_idx=0, **kw)
    _assert_identical(te_, th_, "pure-categorical")


def test_hist_with_row_pruning_still_consistent():
    """Sprint-style pruning under hist (per-tree builder): compaction must
    remap the bucket ids and leave the model unchanged."""
    rng = np.random.default_rng(0)
    n = 2000
    num = rng.normal(size=(n, 4)).astype(np.float32)
    y = (num[:, 0] > 1.2).astype(np.int32)       # skewed: leaves close early
    ds = from_numpy(num, None, y)
    p = tree_lib.TreeParams(max_depth=8, min_records=50, split_mode="hist",
                            num_bins=32)
    base = RandomForest(p, num_trees=2, seed=3).fit(ds)
    import dataclasses
    pruned = RandomForest(dataclasses.replace(p, prune_closed_frac=0.3),
                          num_trees=2, seed=3).fit(ds)
    for ta, tb in zip(base.trees, pruned.trees):
        _assert_identical(ta, tb, "hist-pruned")


def test_hist_gbt_trains():
    rng = np.random.default_rng(1)
    n = 900
    num = rng.normal(size=(n, 4)).astype(np.float32)
    y = (2 * num[:, 0] + num[:, 1] ** 2).astype(np.float32)
    ds = from_numpy(num, None, y, task="regression")
    gbt = GBTModel(GBTParams(num_rounds=10, max_depth=3, learning_rate=0.3,
                             split_mode="hist", num_bins=64)).fit(ds)
    rmse = float(np.sqrt(((gbt.predict(ds.num, ds.cat) - y) ** 2).mean()))
    assert rmse < 0.5 * y.std()


def test_hist_rejects_bad_params():
    with pytest.raises(ValueError):
        tree_lib._tree_setup(jnp.zeros((0, 0), jnp.float32), (),
                             jnp.zeros((4,), jnp.int32),
                             tree_lib.TreeParams(split_mode="planet"))
    with pytest.raises(ValueError):
        tree_lib._tree_setup(jnp.zeros((0, 0), jnp.float32), (),
                             jnp.zeros((4,), jnp.int32),
                             tree_lib.TreeParams(split_mode="hist",
                                                 num_bins=1))


# ---------------------------------------------------------------------------
# Distributed hist supersplit (plumbing; the 8-device run is in
# tests/test_distributed.py under -m slow)
# ---------------------------------------------------------------------------

def test_hist_sharded_supersplit_single_device_mesh():
    """The psum-merged histogram supersplit on a 1x1 mesh must equal the
    local bucket search, end to end through a forest fit."""
    from repro.core import distributed
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(1, 1)
    ds = make_tabular("xor", 600, num_informative=2, num_useless=2, seed=1)
    B = 32
    p = tree_lib.TreeParams(max_depth=4, split_mode="hist", num_bins=B)
    local = RandomForest(p, num_trees=2, seed=11).fit(ds)
    fn = distributed.make_hist_sharded_supersplit(mesh)
    dist = RandomForest(p, num_trees=2, seed=11).fit(ds, supersplit_fn=fn)
    for ta, tb in zip(local.trees, dist.trees):
        _assert_identical(ta, tb, "hist-sharded-1x1")
