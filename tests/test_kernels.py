"""Pallas kernels vs ref.py oracles: shape/dtype sweeps (assignment (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import splits
from repro.kernels import feat_hist, ops, ref


def _mk(seed, n, m, L, C, dup=False):
    rng = np.random.default_rng(seed)
    num = rng.normal(size=(n, m)).astype(np.float32)
    if dup:
        num = np.round(num)                   # heavy ties
    y = rng.integers(0, C, n).astype(np.int32)
    w = rng.integers(0, 3, n).astype(np.float32)
    leaf = rng.integers(0, L + 1, n).astype(np.int32)
    si = np.argsort(num.T, axis=-1, kind="stable").astype(np.int32)
    sv = np.take_along_axis(num.T, si, -1)
    cand = np.ones((m, L + 1), bool)
    cand[:, 0] = False
    return sv, si, leaf, w, y, cand


def _oracle(sv, si, leaf, w, y, cand, L, C, task="classification",
            impurity="gini", min_records=1.0):
    leaf_g, w_g = leaf[si], w[si]
    y_g = y[si].astype(np.float32)

    def tot(lf, ww, yy):
        st = splits.row_stats(jnp.asarray(yy), jnp.asarray(ww), C, task)
        st = jnp.where(((ww > 0) & (lf > 0))[:, None], st, 0.0)
        return jax.ops.segment_sum(st, lf, num_segments=L + 1)

    totals = jax.vmap(tot)(jnp.asarray(leaf_g), jnp.asarray(w_g),
                           jnp.asarray(y_g))
    return ref.split_scan_ref(
        jnp.asarray(sv), jnp.asarray(leaf_g), jnp.asarray(w_g),
        jnp.asarray(y_g), jnp.asarray(cand, np.float32), totals,
        L1=L + 1, s_dim=C if task == "classification" else 3,
        impurity=impurity, task=task, min_records=min_records)


SWEEP = [
    # (n, m, L, C, bn, dup)
    (256, 2, 1, 2, 64, False),
    (500, 3, 5, 3, 128, False),
    (1000, 4, 7, 2, 256, True),
    (777, 2, 3, 4, 128, True),      # n not multiple of bn -> padding path
    (512, 1, 15, 2, 512, False),    # single block
]


@pytest.mark.parametrize("n,m,L,C,bn,dup", SWEEP)
def test_split_scan_kernel_sweep(n, m, L, C, bn, dup):
    sv, si, leaf, w, y, cand = _mk(n + m, n, m, L, C, dup)
    g_k, t_k = ops.split_scan_supersplit(
        jnp.asarray(sv), jnp.asarray(si), jnp.asarray(leaf), jnp.asarray(w),
        jnp.asarray(y), jnp.asarray(cand), L, bn=bn)
    g_r, t_r = _oracle(sv, si, leaf, w, y, cand, L, C)
    gk, gr = np.asarray(g_k), np.asarray(g_r)
    fin = np.isfinite(gr)
    assert (np.isfinite(gk) == fin).all()
    np.testing.assert_allclose(gk[fin], gr[fin], atol=2e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(t_k)[fin], np.asarray(t_r)[fin],
                               atol=1e-4)


def test_split_scan_kernel_regression_task():
    n, m, L = 512, 2, 3
    rng = np.random.default_rng(0)
    num = rng.normal(size=(n, m)).astype(np.float32)
    y = (num[:, 0] * 2 + rng.normal(size=n) * 0.1).astype(np.float32)
    w = np.ones(n, np.float32)
    leaf = rng.integers(1, L + 1, n).astype(np.int32)
    si = np.argsort(num.T, axis=-1, kind="stable").astype(np.int32)
    sv = np.take_along_axis(num.T, si, -1)
    cand = np.ones((m, L + 1), bool); cand[:, 0] = False
    g_k, t_k = ops.split_scan_supersplit(
        jnp.asarray(sv), jnp.asarray(si), jnp.asarray(leaf), jnp.asarray(w),
        jnp.asarray(y), jnp.asarray(cand), L, impurity="variance",
        task="regression", bn=128)
    g_r, t_r = _oracle(sv, si, leaf, w, y, cand, L, 2, task="regression",
                       impurity="variance")
    fin = np.isfinite(np.asarray(g_r))
    np.testing.assert_allclose(np.asarray(g_k)[fin], np.asarray(g_r)[fin],
                               rtol=1e-3, atol=1e-2)


@pytest.mark.parametrize("entropy", ["gini", "entropy"])
def test_split_scan_kernel_impurities(entropy):
    sv, si, leaf, w, y, cand = _mk(11, 384, 2, 3, 2)
    g_k, _ = ops.split_scan_supersplit(
        jnp.asarray(sv), jnp.asarray(si), jnp.asarray(leaf), jnp.asarray(w),
        jnp.asarray(y), jnp.asarray(cand), 3, impurity=entropy, bn=128)
    g_r, _ = _oracle(sv, si, leaf, w, y, cand, 3, 2, impurity=entropy)
    fin = np.isfinite(np.asarray(g_r))
    np.testing.assert_allclose(np.asarray(g_k)[fin], np.asarray(g_r)[fin],
                               atol=2e-3, rtol=1e-3)


@pytest.mark.parametrize("V,bv,bn", [(6, 6, 128), (16, 4, 64), (32, 8, 256)])
def test_cat_hist_kernel_sweep(V, bv, bn):
    n, m, L, C = 512, 3, 4, 3
    rng = np.random.default_rng(V)
    x = rng.integers(0, V, size=(m, n)).astype(np.int32)
    leaf = rng.integers(0, L + 1, n).astype(np.int32)
    w = rng.integers(0, 3, n).astype(np.float32)
    y = rng.integers(0, C, n).astype(np.int32)
    # wb=2 leaf slots and bv values per block: every tiling axis has
    # several blocks, and V % bv != 0 pads the last value block
    tbl_k = feat_hist.feat_hist_pallas(
        jnp.asarray(x), jnp.asarray(leaf), jnp.asarray(w),
        jnp.asarray(y.astype(np.float32)), W=L + 1, V=V, s_dim=C, bn=bn,
        task="classification", interpret=True, plan=(2, bv))
    tbl_r = ref.cat_hist_ref(
        jnp.asarray(x), jnp.asarray(np.broadcast_to(leaf, (m, n))),
        jnp.asarray(np.broadcast_to(w, (m, n))),
        jnp.asarray(np.broadcast_to(y.astype(np.float32), (m, n))),
        L1=L + 1, V=V, s_dim=C)
    np.testing.assert_allclose(np.asarray(tbl_k), np.asarray(tbl_r), atol=1e-4)


# ---------------------------------------------------------------------------
# Interpret-mode compile-cost bound (ROADMAP "kernel-backend compile cost"):
# off-TPU the row-block grid is unrolled at trace time, so the block count
# must stay bounded no matter how large n grows.
# ---------------------------------------------------------------------------

def test_interpret_grid_plan_bounds_block_count():
    for n in (1_000, 100_000, 1_000_000, 10_000_000, 10**9):
        bn, nblocks, gated = ops._interpret_grid_plan(n, 256)
        assert nblocks <= ops._MAX_INTERPRET_ROW_BLOCKS, n
        assert not gated                       # linear kernels never gate
        assert bn * nblocks >= n
        bn_q, nblocks_q, gated_q = ops._interpret_grid_plan(
            n, 256, quadratic=True)
        # quadratic kernels either fit the bounded unroll with a bounded
        # block size, or gate to the jnp fallback — never an unbounded grid
        assert gated_q or (nblocks_q <= ops._MAX_INTERPRET_ROW_BLOCKS
                           and bn_q <= ops._MAX_INTERPRET_BN), n
    # small n: untouched (bit-compatible with the original block schedule)
    assert ops._interpret_grid_plan(1_000, 256) == (256, 4, False)


def test_split_scan_chunked_blocks_match_default(monkeypatch):
    """Forcing the block-growth path (as if n were huge) must reproduce the
    default-schedule splits — same supersplit, bigger blocks."""
    sv, si, leaf, w, y, cand = _mk(5, 640, 2, 3, 2, dup=True)
    args = (jnp.asarray(sv), jnp.asarray(si), jnp.asarray(leaf),
            jnp.asarray(w), jnp.asarray(y), jnp.asarray(cand), 3)
    g0, t0 = ops.split_scan_supersplit(*args, bn=64, num_classes=2)
    monkeypatch.setattr(ops, "_MAX_INTERPRET_ROW_BLOCKS", 2)
    g1, t1 = ops.split_scan_supersplit(*args, bn=64, num_classes=2)
    fin = np.isfinite(np.asarray(g0))
    assert (np.isfinite(np.asarray(g1)) == fin).all()
    np.testing.assert_allclose(np.asarray(g1)[fin], np.asarray(g0)[fin],
                               atol=2e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(t1)[fin], np.asarray(t0)[fin],
                               atol=1e-4)


def test_split_scan_gated_fallback_matches_kernel(monkeypatch):
    """The large-n gate (quadratic block would blow VMEM/compile) answers
    with the exact jnp engine — same splits as the kernel would find."""
    sv, si, leaf, w, y, cand = _mk(9, 512, 2, 4, 3)
    args = (jnp.asarray(sv), jnp.asarray(si), jnp.asarray(leaf),
            jnp.asarray(w), jnp.asarray(y), jnp.asarray(cand), 4)
    g0, t0 = ops.split_scan_supersplit(*args, bn=64, num_classes=3)
    monkeypatch.setattr(ops, "_MAX_INTERPRET_ROW_BLOCKS", 2)
    monkeypatch.setattr(ops, "_MAX_INTERPRET_BN", 128)   # force the gate
    assert ops._interpret_grid_plan(512, 64, quadratic=True)[2]
    g1, t1 = ops.split_scan_supersplit(*args, bn=64, num_classes=3)
    fin = np.isfinite(np.asarray(g0))
    assert (np.isfinite(np.asarray(g1)) == fin).all()
    np.testing.assert_allclose(np.asarray(g1)[fin], np.asarray(g0)[fin],
                               atol=2e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(t1)[fin], np.asarray(t0)[fin],
                               atol=1e-4)


def test_cat_hist_chunked_blocks_exact(monkeypatch):
    """Categorical table block growth is exact (integer sums, order-free)."""
    n, m, L, C, V = 700, 2, 3, 2, 9
    rng = np.random.default_rng(1)
    x = rng.integers(0, V, size=(m, n)).astype(np.int32)
    leaf = rng.integers(0, L + 1, n).astype(np.int32)
    w = rng.integers(0, 3, n).astype(np.float32)
    y = rng.integers(0, C, n).astype(np.int32)
    args = (jnp.asarray(x), jnp.asarray(leaf), jnp.asarray(w),
            jnp.asarray(y))
    t0 = ops.categorical_tables(*args, V=V, Lp=L, bn=64, num_classes=C)
    monkeypatch.setattr(ops, "_MAX_INTERPRET_ROW_BLOCKS", 3)
    t1 = ops.categorical_tables(*args, V=V, Lp=L, bn=64, num_classes=C)
    np.testing.assert_allclose(np.asarray(t1), np.asarray(t0), atol=1e-5)


def test_kernel_backend_in_tree_builder_matches():
    """TreeParams(backend='kernel') builds the same forest as 'scan'."""
    from repro.core import tree as tree_lib
    from repro.core.dataset import from_numpy
    from repro.core.forest import RandomForest
    rng = np.random.default_rng(2)
    n = 600
    num = rng.normal(size=(n, 3)).astype(np.float32)
    yb = (num[:, 0] * num[:, 1] > 0).astype(np.int32)
    ds = from_numpy(num, None, yb)
    a = RandomForest(tree_lib.TreeParams(max_depth=3, backend="kernel"),
                     num_trees=1, seed=3).fit(ds)
    b = RandomForest(tree_lib.TreeParams(max_depth=3, backend="scan"),
                     num_trees=1, seed=3).fit(ds)
    np.testing.assert_array_equal(a.trees[0].feature, b.trees[0].feature)
    np.testing.assert_allclose(a.trees[0].threshold, b.trees[0].threshold,
                               atol=1e-4)
