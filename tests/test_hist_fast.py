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
    fit time instead of being silently ignored;
  * classification tables scatter ONE update per (row, column), at the
    row's class, bit-equal to the S-plane scatter, and the level program
    and the `level.table_updates` counter show that path.
"""
import dataclasses
import functools

import jax
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
# Class path: one update per (row, column) at the row's class
# ---------------------------------------------------------------------------

def _table_case(seed, T, n, m, L, B, discards):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, B, size=(m, n)).astype(np.uint8)
    slots = rng.integers(0 if discards else 1, L + 1,
                         size=(T, n)).astype(np.int32)
    w = rng.poisson(1.0, size=(T, n)).astype(np.float32)  # w = 0 rows too
    assert (w == 0).any() and (slots == 0).any() == discards
    return rng, bins, slots, w


def _tables(bins, slots, w, stats, L, B, T, **kw):
    """T = 1 takes the unbatched form (no tree axis)."""
    if T == 1:
        slots, w, stats = slots[0], w[0], stats[0]
        kw = {k: v[0] if v.ndim == 2 else v for k, v in kw.items()}
    return np.asarray(splits.feature_count_tables(
        jnp.asarray(bins), jnp.asarray(slots), jnp.asarray(w), stats, L, B,
        **kw))


@pytest.mark.parametrize("discards", [False, True])
@pytest.mark.parametrize("T", [1, 2])
@pytest.mark.parametrize("S", [2, 7])
def test_class_path_tables_bit_equal_plane_path(S, T, discards):
    """`labels=y` (w at the row's class, one update per row and column)
    gives the plane scatter's tables bit for bit: Poisson bag weights,
    w = 0 rows, slot-0 discards, class ids off [0, S), class ids shared
    by the trees or given per tree."""
    n, m, L, B = 700, 5, 6, 255
    rng, bins, slots, w = _table_case(S * 10 + T, T, n, m, L, B, discards)
    y = rng.integers(0, S, n).astype(np.int32)
    y[:3] = (-1, S, S + 4)        # off [0, S): one_hot adds nothing
    w[:, :3] = 2.0
    stats = jnp.stack([splits.row_stats(jnp.asarray(y), jnp.asarray(wt), S,
                                        "classification") for wt in w])
    plane = _tables(bins, slots, w, stats, L, B, T)
    assert plane.shape == (T,) * (T > 1) + (m, L + 1, S, B)
    assert plane.sum() > 0
    for labels in (y, np.broadcast_to(y, (T, n))):
        klass = _tables(bins, slots, w, stats, L, B, T,
                        labels=jnp.asarray(labels))
        np.testing.assert_array_equal(klass.view(np.uint32),
                                      plane.view(np.uint32))


@pytest.mark.parametrize("T", [1, 2])
def test_regression_plane_path_unchanged(T):
    """`labels=None` keeps the plane scatter: each tree's [w, wy, wy²]
    tables equal the per-column `categorical_count_table` bit for bit."""
    n, m, L, B = 600, 4, 5, 64
    rng, bins, slots, w = _table_case(40 + T, T, n, m, L, B, True)
    y = rng.normal(size=n).astype(np.float32)
    stats = jnp.stack([splits.row_stats(jnp.asarray(y), jnp.asarray(wt), 3,
                                        "regression") for wt in w])
    got = _tables(bins, slots, w, stats, L, B, T).reshape(T, m, L + 1, 3, B)
    for t in range(T):
        want = np.stack([np.asarray(splits.categorical_count_table(
            jnp.asarray(bins[j].astype(np.int32)), jnp.asarray(slots[t]),
            jnp.asarray(w[t]), stats[t], L, B)) for j in range(m)])
        np.testing.assert_array_equal(got[t].view(np.uint32),
                                      want.view(np.uint32))


def _f32_scatter_add_sizes(jaxpr):
    """Updates' element count of every f32 scatter-add in a jaxpr and in
    the jaxprs nested in it (jit, lax.map, cond)."""
    sizes = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scatter-add":
            upd = eqn.invars[2].aval
            if upd.dtype == jnp.float32:
                sizes.append(upd.size)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    sizes += _f32_scatter_add_sizes(sub)
    return sizes


@pytest.mark.parametrize("task", ["classification", "regression"])
def test_level_step_scatters_one_update_per_row_and_column(monkeypatch,
                                                           task):
    """The traced hist level program's table scatter has m·rows updates
    per tree for classification (rows = n at the root, n//2 once
    subtraction compacts the build rows), never m·S·rows; regression's
    plane scatter keeps m·3·rows."""
    n, m, C = 520, 5, 7
    rng = np.random.default_rng(6)
    num = rng.normal(size=(n, m)).astype(np.float32)
    if task == "classification":
        y = (np.digitize(num[:, 0], [-1, -.5, 0, .3, .6, 1])
             + (num[:, 1] > 0)) % C
        ds = from_numpy(num, None, y.astype(np.int32))
        assert ds.num_classes == C
        p = tree_lib.TreeParams(max_depth=3, split_mode="hist", num_bins=16)
    else:
        ds = from_numpy(num, None, (num[:, 0] + num[:, 1] ** 2)
                        .astype(np.float32), task="regression")
        p = tree_lib.TreeParams(max_depth=3, split_mode="hist", num_bins=16,
                                task="regression", impurity="variance")
    # trees in sequence inside the program, as at the benchmark's sizes
    monkeypatch.setattr(tree_lib, "_BATCH_VMAP_ELEMS", 0)
    calls = []
    step = tree_lib._fused_level_step_batched

    def spy(*args, **kw):
        calls.append((args, kw))
        return step(*args, **kw)
    monkeypatch.setattr(tree_lib, "_fused_level_step_batched", spy)
    RandomForest(p, num_trees=2, seed=1).fit(ds)
    assert len(calls) >= 2
    subtracted = 0
    for args, kw in calls:
        sizes = _f32_scatter_add_sizes(
            jax.make_jaxpr(functools.partial(step, **kw))(*args).jaxpr)
        if task == "classification":
            rows = n // 2 if kw["subtract"] else n
            subtracted += kw["subtract"]
            assert m * rows in sizes, (kw, sizes)
            assert m * C * rows not in sizes, (kw, sizes)
        else:
            assert not kw["subtract"]
            assert m * 3 * n in sizes, (kw, sizes)
    assert subtracted or task == "regression"


@pytest.mark.parametrize("task,mode,subtract", [
    ("classification", "hist", False),
    ("classification", "hist", True),
    ("regression", "hist", False),
    ("classification", "exact", False),
])
def test_table_updates_counter(skewed_ds, task, mode, subtract):
    """`level.table_updates` per tree-level is m·rows for classification
    (n//2 rows under subtraction past the root), m·S·rows for regression
    (S = 3) and 0 where no bin table is scattered."""
    n, m = skewed_ds.n, skewed_ds.m_num
    ds = skewed_ds
    kw = dict(max_depth=4, split_mode=mode, num_bins=16,
              hist_subtract=subtract)
    if task == "regression":
        ds = from_numpy(np.asarray(ds.num), None,
                        np.asarray(ds.num)[:, 0] * 2.0, task="regression")
        kw.update(task="regression", impurity="variance")
    T = 2
    before = obs.counters()
    RandomForest(tree_lib.TreeParams(**kw), num_trees=T, seed=3,
                 tree_batch=T).fit(ds)
    d = obs.delta(before)
    tree_rows, updates = d["level.tree_rows"], d.get("level.table_updates")
    assert tree_rows % n == 0 and tree_rows > T * n    # past the root
    if mode == "exact":
        assert updates == 0
    elif task == "regression":
        assert updates == m * 3 * tree_rows
    elif not subtract:
        assert updates == m * tree_rows
    else:
        assert updates == m * (T * n + (tree_rows // n - T) * (n // 2))


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
