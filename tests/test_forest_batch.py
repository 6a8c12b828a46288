"""Multi-tree batched level step (tree.build_forest, DESIGN.md §3).

The contract under test: `RandomForest.fit` with a tree batch issues ONE
jitted level program per depth per batch, never falls back to per-tree
dispatches, and produces trees BIT-IDENTICAL to the per-tree fused builder
and to `build_tree_reference` — for every backend, for both batched
lowerings (vmap / lax.map), and for forests whose trees finish at
different depths.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import bagging, presort, tree as tree_lib
from repro.core.dataset import from_numpy
from repro.core.forest import RandomForest
from repro.data.synthetic import make_tabular


def _presorted(ds):
    if ds.m_num:
        si = presort.presort_columns(ds.num)
        return presort.gather_sorted(ds.num, si), si
    return (jnp.zeros((0, ds.n), jnp.float32), jnp.zeros((0, ds.n), jnp.int32))


def _build_kw(ds, seed=5):
    sv, si = _presorted(ds)
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
    # seed chosen so the 4 trees below finish at different depths under
    # the installed jax's bagging draws (one tree closes at depth 3)
    rng = np.random.default_rng(10)
    n = 1100
    num = rng.normal(size=(n, 4)).astype(np.float32)
    cat = rng.integers(0, 5, size=(n, 2)).astype(np.int32)
    y = ((num[:, 0] > 0) ^ (cat[:, 0] >= 3)).astype(np.int32)
    return from_numpy(num, cat, y)


@pytest.mark.parametrize("backend", ["segment", "scan", "kernel"])
def test_batched_matches_reference_per_tree(mixed_ds, backend):
    """Bit-exact parity batched vs per-tree fused vs reference, with trees
    that finish at different depths (early close under max_depth)."""
    kw = _build_kw(mixed_ds)
    p = tree_lib.TreeParams(max_depth=4, backend=backend)
    trees, _ = tree_lib.build_forest(params=p, tree_indices=range(4), **kw)
    depths = {t.max_depth_reached for t in trees}
    assert len(depths) > 1, "fixture must exercise uneven finish depths"
    for t in range(4):
        ref, _ = tree_lib.build_tree_reference(params=p, tree_idx=t, **kw)
        fused, _ = tree_lib.build_tree(params=p, tree_idx=t, **kw)
        _assert_identical(trees[t], ref, f"{backend}/tree{t}/batched-vs-ref")
        _assert_identical(fused, ref, f"{backend}/tree{t}/fused-vs-ref")


def test_batched_map_lowering_matches_reference(mixed_ds, monkeypatch):
    """The large-batch lax.map lowering is bit-exact too (forced on)."""
    monkeypatch.setattr(tree_lib, "_BATCH_VMAP_ELEMS", 0)
    tree_lib._fused_level_step_batched.clear_cache()
    try:
        kw = _build_kw(mixed_ds)
        p = tree_lib.TreeParams(max_depth=4)
        trees, _ = tree_lib.build_forest(params=p, tree_indices=range(3), **kw)
        for t in range(3):
            ref, _ = tree_lib.build_tree_reference(params=p, tree_idx=t, **kw)
            _assert_identical(trees[t], ref, f"map/tree{t}")
    finally:
        tree_lib._fused_level_step_batched.clear_cache()


def test_batched_regression_matches_reference():
    rng = np.random.default_rng(1)
    n = 900
    num = rng.normal(size=(n, 4)).astype(np.float32)
    y = (2 * num[:, 0] + num[:, 1] ** 2
         + 0.1 * rng.normal(size=n)).astype(np.float32)
    ds = from_numpy(num, None, y, task="regression")
    kw = _build_kw(ds, seed=2)
    p = tree_lib.TreeParams(max_depth=5, impurity="variance",
                            task="regression", bagging="none")
    trees, _ = tree_lib.build_forest(params=p, tree_indices=range(3), **kw)
    for t in range(3):
        ref, _ = tree_lib.build_tree_reference(params=p, tree_idx=t, **kw)
        _assert_identical(trees[t], ref, f"regression/tree{t}")


def test_batched_pure_categorical_matches_reference():
    rng = np.random.default_rng(0)
    n = 700
    cat = rng.integers(0, 6, size=(n, 3)).astype(np.int32)
    y = ((cat[:, 0] % 2) ^ (cat[:, 1] >= 3)).astype(np.int32)
    ds = from_numpy(None, cat, y)
    kw = _build_kw(ds)
    p = tree_lib.TreeParams(max_depth=4)
    trees, _ = tree_lib.build_forest(params=p, tree_indices=range(3), **kw)
    for t in range(3):
        ref, _ = tree_lib.build_tree_reference(params=p, tree_idx=t, **kw)
        _assert_identical(trees[t], ref, f"categorical/tree{t}")


def test_fit_chunking_and_auto_batch(mixed_ds):
    """tree_batch chunking covers every tree; auto heuristic is identical."""
    p = tree_lib.TreeParams(max_depth=4)
    a = RandomForest(p, num_trees=7, seed=1, tree_batch=3).fit(mixed_ds)
    b = RandomForest(p, num_trees=7, seed=1, tree_batch=1).fit(mixed_ds)
    c = RandomForest(p, num_trees=7, seed=1).fit(mixed_ds)   # auto
    assert len(a.trees) == len(b.trees) == len(c.trees) == 7
    for ta, tb, tc in zip(a.trees, b.trees, c.trees):
        _assert_identical(ta, tb, "chunk3-vs-pertree")
        _assert_identical(tc, tb, "auto-vs-pertree")
    assert a.packed is not None and a.packed.num_trees == 7


def test_fit_level_stats_match_per_tree(mixed_ds):
    p = tree_lib.TreeParams(max_depth=5)
    a = RandomForest(p, num_trees=3, seed=0, tree_batch=3).fit(
        mixed_ds, collect_stats=True)
    b = RandomForest(p, num_trees=3, seed=0, tree_batch=1).fit(
        mixed_ds, collect_stats=True)
    assert a.level_stats == b.level_stats


def test_one_level_program_per_depth_trace_counted(mixed_ds):
    """fit(n_trees=16) issues ONE batched jitted program per depth level —
    dispatch-counted AND trace-counted — with zero per-tree dispatches."""
    p = tree_lib.TreeParams(max_depth=4, backend="segment")
    rf = RandomForest(p, num_trees=16, seed=0, tree_batch=16)
    rf.fit(mixed_ds)                                   # warm the jit caches

    calls0 = obs.counter("level.dispatches")
    steps0 = obs.counter("level.tree_dispatches")
    traces0 = obs.counter("level.traces")
    rf2 = RandomForest(p, num_trees=16, seed=0, tree_batch=16).fit(mixed_ds)
    calls = obs.counter("level.dispatches") - calls0
    D = max(t.max_depth_reached for t in rf2.trees)
    # one dispatch per depth level actually run, for the whole 16-tree batch
    assert D <= calls <= p.max_depth + 1, (calls, D)
    # no per-tree fused dispatches, no retraces on the warm cache
    assert obs.counter("level.tree_dispatches") == steps0
    assert obs.counter("level.traces") == traces0
    for ta, tb in zip(rf.trees, rf2.trees):
        _assert_identical(ta, tb, "warm-vs-cold")


def test_bag_counts_forest_bitexact_per_tree():
    """The stacked bootstrap draw equals the per-tree draw, per tree."""
    for mode in ("poisson", "multinomial", "none"):
        wb = np.asarray(bagging.bag_counts_forest(
            3, jnp.arange(5), 1000, mode))
        for t in range(5):
            np.testing.assert_array_equal(
                wb[t], np.asarray(bagging.bag_counts(3, t, 1000, mode)),
                err_msg=f"{mode}/tree{t}")


def test_candidate_features_padding_independent():
    """Row h of the candidate mask must not depend on the padded leaf count
    — the property that makes batch-max padding bit-safe (DESIGN.md §3)."""
    key = jax.random.PRNGKey(42)
    small = np.asarray(bagging.candidate_features(key, 2, 4, 10, 3))
    large = np.asarray(bagging.candidate_features(key, 2, 32, 10, 3))
    np.testing.assert_array_equal(small, large[:4])
    # usb draws one shared row; also padding-independent
    su = np.asarray(bagging.candidate_features(key, 2, 4, 10, 3, usb=True))
    lu = np.asarray(bagging.candidate_features(key, 2, 32, 10, 3, usb=True))
    np.testing.assert_array_equal(su, lu[:4])


def test_device_resident_pruning_still_exact():
    """prune_closed_frac (now a device-side closed-prefix slice) must not
    change the model, batched or not."""
    rng = np.random.default_rng(0)
    n = 2000
    num = rng.normal(size=(n, 4)).astype(np.float32)
    y = (num[:, 0] > 1.2).astype(np.int32)   # skewed: leaves close early
    ds = from_numpy(num, None, y)
    base = RandomForest(tree_lib.TreeParams(max_depth=8, min_records=50),
                        num_trees=2, seed=3).fit(ds)
    for backend in ("segment", "scan"):
        pruned = RandomForest(
            tree_lib.TreeParams(max_depth=8, min_records=50, backend=backend,
                                prune_closed_frac=0.3),
            num_trees=2, seed=3).fit(ds)
        for ta, tb in zip(base.trees, pruned.trees):
            assert ta.num_nodes == tb.num_nodes
            np.testing.assert_array_equal(ta.feature, tb.feature)
            np.testing.assert_allclose(ta.threshold, tb.threshold, atol=1e-4)


def test_batched_pruning_stays_batched_and_exact():
    """Sprint pruning no longer downgrades to the per-tree builder: the
    batched driver drops rows closed in EVERY tree of the batch (a
    result-invariant subset) and keeps issuing one level program per depth
    for the whole batch — bit-identical to the unpruned forest."""
    rng = np.random.default_rng(0)
    n = 2000
    num = rng.normal(size=(n, 4)).astype(np.float32)
    y = (num[:, 0] > 1.2).astype(np.int32)   # skewed: leaves close early
    ds = from_numpy(num, None, y)
    base = RandomForest(tree_lib.TreeParams(max_depth=8, min_records=50),
                        num_trees=3, seed=3, tree_batch=3).fit(ds)
    for backend in ("segment", "scan"):
        calls0 = obs.counter("level.dispatches")
        steps0 = obs.counter("level.tree_dispatches")
        pruned = RandomForest(
            tree_lib.TreeParams(max_depth=8, min_records=50, backend=backend,
                                prune_closed_frac=0.3),
            num_trees=3, seed=3, tree_batch=3).fit(ds)
        assert obs.counter("level.dispatches") > calls0, backend
        assert obs.counter("level.tree_dispatches") == steps0, backend
        for ta, tb in zip(base.trees, pruned.trees):
            _assert_identical(ta, tb, f"batched-pruned/{backend}")


def test_legacy_supersplit_fn_warns_and_uses_per_tree_builder(mixed_ds):
    """A bare supersplit_fn closure (the pre-SplitEngine API) cannot ride
    the batched builder: fit must say so (UserWarning) and fall back to
    the per-tree path — producing the identical forest."""
    import jax

    from repro.core import splits

    def legacy_fn(sorted_vals, sorted_idx, leaf_of, w, stats, cand, Lp,
                  impurity, task, min_records):
        def per_col(v, s, c):
            return splits.best_numeric_split_segment(
                v, leaf_of[s], w[s], stats[s], c, Lp, impurity, task,
                min_records)
        return jax.vmap(per_col)(sorted_vals, sorted_idx, cand)

    p = tree_lib.TreeParams(max_depth=3)
    plain = RandomForest(p, num_trees=2, seed=4).fit(mixed_ds)
    calls0 = obs.counter("level.dispatches")
    steps0 = obs.counter("level.tree_dispatches")
    with pytest.warns(UserWarning, match="per-tree builder"):
        legacy = RandomForest(p, num_trees=2, seed=4).fit(
            mixed_ds, supersplit_fn=legacy_fn)
    # no batched programs, per-tree dispatches
    assert obs.counter("level.dispatches") == calls0
    assert obs.counter("level.tree_dispatches") > steps0
    for ta, tb in zip(plain.trees, legacy.trees):
        _assert_identical(ta, tb, "legacy-vs-plain")


def test_forest_smoke_bench_runs(tmp_path, monkeypatch):
    """The forest batching benchmark's smoke mode runs in seconds and emits
    a well-formed BENCH_forest_batch.json."""
    out = tmp_path / "BENCH_forest_batch.json"
    monkeypatch.setenv("BENCH_FOREST_BATCH_JSON", str(out))
    import importlib
    from benchmarks import forest_batch_bench
    importlib.reload(forest_batch_bench)
    report = forest_batch_bench.run(smoke=True)
    assert out.exists()
    assert report["smoke"] is True
    for point in report["points"]:
        assert point["per_tree_s"] > 0 and point["batched_s"] > 0
        assert np.isfinite(point["speedup"])
        assert point["level_programs_batched"] < point["level_programs_per_tree"]
