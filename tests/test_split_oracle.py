"""Exact-split search vs a brute-force oracle — the suite that certifies
"exact" (the paper's central claim) for every numeric engine and the
categorical table scorer.

The oracle is a tiny O(n·S) numpy implementation: per leaf, sort the
in-bag rows once, sweep cumulative histograms over the boundaries between
consecutive distinct values, and keep the first-best boundary (the
engines' scan-order tie-break).  Deterministic seed-parametrized cases run
in tier-1 (no hypothesis needed); the `-m hypothesis` sweep drives the same
checker from `@given` seeds under the fixed derandomized profile
(tests/conftest.py).

Adversarial structure baked into every generated dataset: duplicated
values (ties), a constant column, a single-class leaf, zero-weight
(bagged-out) rows, a fully bagged-out leaf, and closed (leaf 0) rows.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import splits
from repro.kernels import ops as kops

try:
    from hypothesis import given, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # optional dev dependency
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# The oracle
# ---------------------------------------------------------------------------

def _imp(h, impurity):
    """Weighted (N·) impurity of histogram(s) h (..., S), float64."""
    h = np.asarray(h, np.float64)
    n = h.sum(-1)
    if impurity == "gini":
        return n - np.divide((h * h).sum(-1), n, out=np.zeros_like(n),
                             where=n > 0)
    if impurity == "entropy":
        p = np.divide(h, n[..., None], out=np.zeros_like(h),
                      where=n[..., None] > 0)
        plogp = np.where(h > 0, p * np.log(np.maximum(p, 1e-300)), 0.0)
        return -(n * plogp.sum(-1))
    if impurity == "variance":
        w, wy, wy2 = h[..., 0], h[..., 1], h[..., 2]
        return np.maximum(wy2 - np.divide(wy * wy, w, out=np.zeros_like(w),
                                          where=w > 0), 0.0)
    raise ValueError(impurity)


def _row_stats_np(y, w, C, task):
    if task == "classification":
        s = np.zeros((len(y), C), np.float64)
        s[np.arange(len(y)), y] = w
        return s
    y = np.asarray(y, np.float64)
    return np.stack([w, w * y, w * y * y], -1)


def oracle_numeric(vals, y, w, C, impurity="gini", task="classification",
                   min_records=1.0):
    """Best (gain, threshold) for ONE leaf's rows, O(n·S).

    One sort, then a cumulative-histogram sweep over the midpoints between
    consecutive distinct in-bag values; first boundary wins ties (the
    engines' scan order).  Returns (-inf, 0.0) when no valid split exists.
    """
    inb = w > 0
    vals, y, w = vals[inb], y[inb], w[inb]
    if len(vals) < 2:
        return -np.inf, 0.0
    order = np.argsort(vals, kind="stable")
    vals, stats = vals[order], _row_stats_np(y[order], w[order], C, task)
    total = stats.sum(0)
    prefix = np.cumsum(stats, 0)                   # left of cut after row k
    cnt = (lambda h: h.sum(-1)) if task == "classification" \
        else (lambda h: h[..., 0])
    best_g, best_t = -np.inf, 0.0
    for k in range(len(vals) - 1):
        if vals[k + 1] <= vals[k]:
            continue                               # not a distinct boundary
        left, right = prefix[k], total - prefix[k]
        if cnt(left) < min_records or cnt(right) < min_records:
            continue
        g = (_imp(total, impurity) - _imp(left, impurity)
             - _imp(right, impurity))
        if g > best_g:                             # strict: first max wins
            best_g = g
            best_t = (float(vals[k]) + float(vals[k + 1])) / 2.0
    return best_g, best_t


def oracle_gain_at(vals, y, w, C, thr, impurity="gini",
                   task="classification"):
    """Gain of the partition (x <= thr) for one leaf's in-bag rows."""
    inb = w > 0
    vals, y, w = vals[inb], y[inb], w[inb]
    stats = _row_stats_np(y, w, C, task)
    left = stats[vals <= thr].sum(0)
    right = stats[vals > thr].sum(0)
    return (_imp(left + right, impurity) - _imp(left, impurity)
            - _imp(right, impurity))


# ---------------------------------------------------------------------------
# Adversarial dataset generator (shared by deterministic + hypothesis runs)
# ---------------------------------------------------------------------------

def make_case(seed, n=260, L=4, C=3, m=3):
    """Random (num (n, m), leaf, w, y) with every edge case baked in:
    column 0 tied (coarse grid), column 1 CONSTANT, leaf 1 single-class,
    leaf 2 fully bagged out, plus closed rows (leaf 0) and w == 0 rows."""
    rng = np.random.default_rng(seed)
    num = rng.normal(size=(n, m)).astype(np.float32)
    num[:, 0] = np.round(num[:, 0] * 2) / 2        # heavy ties
    num[:, 1] = 1.5                                # constant column
    leaf = rng.integers(0, L + 1, n).astype(np.int32)
    w = rng.integers(0, 3, n).astype(np.float32)   # zero-weight rows
    y = rng.integers(0, C, n).astype(np.int32)
    y[leaf == 1] = C - 1                           # single-class leaf
    w[leaf == 2] = 0.0                             # fully bagged-out leaf
    return num, leaf, w, y


def _engine_supersplit(backend, num, leaf, w, y, C, Lp, impurity,
                       min_records, task="classification"):
    """Run one numeric engine over all columns; returns (m, L+1) g / t."""
    labels = y.astype(np.float32) if task == "regression" else y
    stats = splits.row_stats(jnp.asarray(labels), jnp.asarray(w), C, task)
    m = num.shape[1]
    si = np.argsort(num.T, axis=-1, kind="stable").astype(np.int32)
    sv = np.take_along_axis(num.T, si, -1)
    cand = np.ones((m, Lp + 1), bool)
    cand[:, 0] = False

    if backend == "kernel":
        g, t = kops.split_scan_supersplit(
            jnp.asarray(sv), jnp.asarray(si), jnp.asarray(leaf),
            jnp.asarray(w), jnp.asarray(labels), jnp.asarray(cand), Lp,
            impurity, task, min_records, num_classes=C)
        return np.asarray(g), np.asarray(t)
    if backend == "leaf_ordered":
        ord_idx = np.stack([np.argsort(leaf[si[j]], kind="stable")
                            for j in range(m)])
        ord_idx = np.take_along_axis(si, ord_idx, -1)   # (leaf, value) order
        lf_pos = leaf[ord_idx[0]]
        inbag = (w > 0)[ord_idx] & (lf_pos > 0)[None]
        vals = np.take_along_axis(num.T, ord_idx, -1)
        row_counts = np.bincount(lf_pos, minlength=Lp + 1).astype(np.int32)
        g, t = splits.best_numeric_split_leaf_ordered(
            jnp.asarray(vals), jnp.asarray(lf_pos), jnp.asarray(inbag),
            stats.T[:, jnp.asarray(ord_idx)], jnp.asarray(cand), Lp, impurity,
            task, min_records, totals=None,
            row_counts=jnp.asarray(row_counts))
        return np.asarray(g), np.asarray(t)

    fn = splits.NUMERIC_BACKENDS[backend]

    def per_col(j):
        s = si[j]
        return fn(jnp.asarray(sv[j]), jnp.asarray(leaf[s]),
                  jnp.asarray(w[s]), stats[jnp.asarray(s)],
                  jnp.asarray(cand[j]), Lp, impurity, task, min_records)
    outs = [per_col(j) for j in range(m)]
    return (np.stack([np.asarray(g) for g, _ in outs]),
            np.stack([np.asarray(t) for _, t in outs]))


ALL_ENGINES = ["scan", "segment", "leaf_ordered", "kernel"]


def check_against_oracle(backend, seed, impurity="gini", min_records=1.0):
    num, leaf, w, y = make_case(seed)
    L, C = int(leaf.max()), int(y.max()) + 1
    if L == 0:
        return
    g, t = _engine_supersplit(backend, num, leaf, w, y, C, L, impurity,
                              min_records)
    for j in range(num.shape[1]):
        for h in range(1, L + 1):
            sel = leaf == h
            bg, _ = oracle_numeric(num[sel, j], y[sel], w[sel], C,
                                   impurity, min_records=min_records)
            ctx = f"{backend}/seed{seed}/col{j}/leaf{h}"
            if not np.isfinite(bg):
                assert not np.isfinite(g[j, h]), ctx
                continue
            assert np.isfinite(g[j, h]), ctx
            np.testing.assert_allclose(g[j, h], bg, rtol=1e-4, atol=1e-4,
                                       err_msg=ctx)
            # tie-robust threshold check: the engine's threshold must
            # ACHIEVE the oracle's best gain (equal-gain boundaries may
            # legitimately differ in the last ulp of the gain comparison)
            ga = oracle_gain_at(num[sel, j], y[sel], w[sel], C, t[j, h],
                                impurity)
            np.testing.assert_allclose(ga, bg, rtol=1e-4, atol=1e-4,
                                       err_msg=ctx + "/thr")
            # and must separate two observed in-bag values
            iv = num[sel & (w > 0), j]
            assert iv.min() <= t[j, h] < iv.max(), ctx


# ---------------------------------------------------------------------------
# Deterministic tier-1 oracle coverage (no hypothesis required)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ALL_ENGINES)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_numeric_engines_match_oracle(backend, seed):
    check_against_oracle(backend, seed)


@pytest.mark.parametrize("backend", ALL_ENGINES)
def test_numeric_engines_match_oracle_entropy_min_records(backend):
    check_against_oracle(backend, 7, impurity="entropy", min_records=5.0)


@pytest.mark.parametrize("backend", ["scan", "segment", "leaf_ordered",
                                     "kernel"])
def test_regression_engines_match_oracle(backend):
    rng = np.random.default_rng(11)
    n, L = 220, 3
    num = rng.normal(size=(n, 2)).astype(np.float32)
    num[:, 0] = np.round(num[:, 0] * 2) / 2
    leaf = rng.integers(0, L + 1, n).astype(np.int32)
    w = rng.integers(0, 3, n).astype(np.float32)
    y = (num[:, 0] * 2 + rng.normal(size=n) * 0.3).astype(np.float32)
    g, t = _engine_supersplit(num=num, leaf=leaf, w=w, y=y, C=2, Lp=L,
                              backend=backend, impurity="variance",
                              min_records=1.0, task="regression")
    for j in range(2):
        for h in range(1, L + 1):
            sel = leaf == h
            bg, _ = oracle_numeric(num[sel, j], y[sel], w[sel], 2,
                                   "variance", "regression")
            ctx = f"{backend}/col{j}/leaf{h}"
            if not np.isfinite(bg):
                assert not np.isfinite(g[j, h]), ctx
                continue
            np.testing.assert_allclose(g[j, h], bg, rtol=1e-3, atol=1e-3,
                                       err_msg=ctx)
            ga = oracle_gain_at(num[sel, j], y[sel], w[sel], 2, t[j, h],
                                "variance", "regression")
            np.testing.assert_allclose(ga, bg, rtol=1e-3, atol=1e-3,
                                       err_msg=ctx + "/thr")


def test_categorical_table_scorer_binary_exhaustive():
    """Binary classification, small arity: the Breiman-ordered prefix cuts
    must find the best of ALL 2^(V-1) subsets — checked from the same
    count-table input the fused step feeds the scorer."""
    for seed in (0, 3):
        rng = np.random.default_rng(seed)
        n, L, V = 300, 3, 5
        x = rng.integers(0, V, n).astype(np.int32)
        leaf = rng.integers(0, L + 1, n).astype(np.int32)
        w = rng.integers(0, 3, n).astype(np.float32)
        y = rng.integers(0, 2, n).astype(np.int32)
        y[leaf == 1] = 1                              # single-class leaf
        stats = splits.row_stats(jnp.asarray(y), jnp.asarray(w), 2,
                                 "classification")
        table = splits.categorical_count_table(
            jnp.asarray(x), jnp.asarray(leaf), jnp.asarray(w), stats, L, V)
        cand = jnp.asarray([False] + [True] * L)
        g, mask = splits.best_categorical_split_from_table(table, cand)
        g, mask = np.asarray(g), np.asarray(mask)
        tb = np.asarray(table, np.float64).transpose(0, 2, 1)   # (L+1, V, S)
        for h in range(1, L + 1):
            total = tb[h].sum(0)
            best = -np.inf
            for subset in range(1, 2 ** V - 1):
                in_s = np.array([(subset >> v) & 1 for v in range(V)], bool)
                hl = tb[h][in_s].sum(0)
                hr = total - hl
                if hl.sum() < 1 or hr.sum() < 1:
                    continue
                best = max(best, _imp(total, "gini") - _imp(hl, "gini")
                           - _imp(hr, "gini"))
            ctx = f"seed{seed}/leaf{h}"
            if not np.isfinite(best):
                assert not np.isfinite(g[h]), ctx
                continue
            np.testing.assert_allclose(g[h], best, rtol=1e-4, atol=1e-4,
                                       err_msg=ctx)
            # the reported mask must achieve the reported gain
            hl = tb[h][mask[h]].sum(0)
            gm = (_imp(total, "gini") - _imp(hl, "gini")
                  - _imp(total - hl, "gini"))
            np.testing.assert_allclose(gm, best, rtol=1e-4, atol=1e-4,
                                       err_msg=ctx + "/mask")


def test_oracle_on_degenerate_leaves():
    """Constant column / single distinct value / all-zero weights -> -inf."""
    for backend in ALL_ENGINES:
        num = np.full((40, 1), 2.5, np.float32)
        leaf = np.ones(40, np.int32)
        w = np.ones(40, np.float32)
        y = np.arange(40, dtype=np.int64).astype(np.int32) % 2
        g, _ = _engine_supersplit(backend, num, leaf, w, y, 2, 1, "gini", 1.0)
        assert not np.isfinite(g[0, 1]), backend
        w0 = np.zeros(40, np.float32)
        g, _ = _engine_supersplit(backend, num, leaf, w0, y, 2, 1, "gini", 1.0)
        assert not np.isfinite(g[0, 1]), backend


# ---------------------------------------------------------------------------
# Streamed histogram accumulation vs the brute-force oracle
# (the out-of-core path: tables built chunk by chunk, DESIGN.md §8)
# ---------------------------------------------------------------------------

def test_hist_scorer_on_streamed_tables_matches_oracle():
    """Hist-mode scoring from CHUNK-ACCUMULATED count tables equals the
    numpy oracle restricted to bucket-boundary thresholds — the same
    adversarial cases (ties, constant column, bagged-out leaf) as the
    exact engines, with the tables built over uneven chunk boundaries
    exactly like `build_forest_streamed` builds them."""
    from repro.core import presort
    B = 16
    for seed in (0, 5):
        num, leaf, w, y = make_case(seed)
        n, m = num.shape
        L, C = int(leaf.max()), int(y.max()) + 1
        si = presort.presort_columns(jnp.asarray(num))
        sv = presort.gather_sorted(jnp.asarray(num), si)
        edges = np.asarray(presort.quantize_edges(sv, B))
        bins = presort.bin_block(num, edges)               # (m, n)
        stats = splits.row_stats(jnp.asarray(y), jnp.asarray(w), C,
                                 "classification")
        table = np.zeros((m, L + 1, C, B), np.float32)
        for lo in range(0, n, 83):                         # uneven tail
            hi = min(lo + 83, n)
            table += np.asarray(splits.feature_count_tables(
                jnp.asarray(np.ascontiguousarray(bins[:, lo:hi])),
                jnp.asarray(leaf[lo:hi]), jnp.asarray(w[lo:hi]),
                stats[lo:hi], L, B))
        cand = jnp.asarray([False] + [True] * L)
        for j in range(m):
            g, cut = splits.best_numeric_split_histogram(
                jnp.asarray(table[j]), cand)
            g, cut = np.asarray(g), np.asarray(cut)
            for h in range(1, L + 1):
                sel = leaf == h
                vj, yj, wj = num[sel, j], y[sel], w[sel]
                best = -np.inf
                for b in range(B - 1):                     # boundary sweep
                    thr = edges[j, b]
                    nl = wj[(vj <= thr) & (wj > 0)].sum()
                    nr = wj[(vj > thr) & (wj > 0)].sum()
                    if nl < 1 or nr < 1:
                        continue
                    gb = oracle_gain_at(vj, yj, wj, C, thr)
                    if gb > best:                          # first max wins
                        best = gb
                ctx = f"seed{seed}/col{j}/leaf{h}"
                if not np.isfinite(best):
                    assert not np.isfinite(g[h]), ctx
                    continue
                assert np.isfinite(g[h]), ctx
                np.testing.assert_allclose(g[h], best, rtol=1e-4,
                                           atol=1e-4, err_msg=ctx)
                # the decoded float threshold reproduces the scored
                # partition (bin <= b  <=>  x <= edges[b])
                ga = oracle_gain_at(vj, yj, wj, C, edges[j, int(cut[h])])
                np.testing.assert_allclose(ga, best, rtol=1e-4, atol=1e-4,
                                           err_msg=ctx + "/thr")


# ---------------------------------------------------------------------------
# Whole-tree oracle: a recursive numpy reference builder vs build_tree
# (ROADMAP "Exact-oracle suite follow-up")
# ---------------------------------------------------------------------------

def _np_bag_counts(seed, tree_idx, n, mode):
    """The seeded bootstrap weights as numpy (the draw itself is pinned by
    the deterministic PRNG; the oracle consumes, never re-derives it)."""
    from repro.core import bagging
    return np.asarray(bagging.bag_counts(seed, tree_idx, n, mode))


def _np_candidates(seed, tree_idx, depth, num_leaves, m, m_prime):
    """Per-leaf candidate masks as numpy (padding-independent draw)."""
    import jax
    from repro.core import bagging
    fkey = jax.random.fold_in(jax.random.PRNGKey(seed ^ 0x5EED), tree_idx)
    return np.asarray(bagging.candidate_features(
        fkey, depth, num_leaves, m, m_prime))


class _RefTree:
    """Flat arrays grown by the reference builder (mirrors tree_lib.Tree)."""

    def __init__(self, C):
        self.feature, self.threshold, self.children = [], [], []
        self.value, self.n_node, self.gain, self.depth = [], [], [], []
        self._C = C

    def new_node(self, depth):
        self.feature.append(-1)
        self.threshold.append(np.float32(0.0))
        self.children.append([-1, -1])
        self.value.append(np.zeros(self._C, np.float32))
        self.n_node.append(0.0)
        self.gain.append(0.0)
        self.depth.append(depth)
        return len(self.feature) - 1


def build_tree_oracle(num, y, params, seed, tree_idx, C):
    """Recursive (level-recursion) numpy reference builder for EXACT mode.

    sklearn-style per-node exhaustive search — every candidate feature of
    every open leaf is scored by the O(n·S) `oracle_numeric` sweep, the
    first-best feature wins (the engines' argmax order), children are
    numbered left-to-right in leaf order — with zero shared code with the
    jitted engines beyond the seeded draws it consumes.  Numeric-only
    datasets (the categorical scorer has its own exhaustive oracle above).
    """
    n, m = num.shape
    task, imp = params.task, params.impurity
    m_prime = params.num_candidates or max(
        1, int(np.ceil(np.sqrt(m))))
    w = _np_bag_counts(seed, tree_idx, n, params.bagging)
    ref = _RefTree(max(C, 2) if task == "classification" else 1)
    root = ref.new_node(0)

    def node_value(node, rows):
        stats = _row_stats_np(y[rows], w[rows], C, task).sum(0)
        cnt = stats.sum() if task == "classification" else stats[0]
        ref.n_node[node] = float(cnt)
        if task == "classification":
            ref.value[node] = (stats.astype(np.float32)
                               / np.float32(max(cnt, 1e-12)))
        else:
            ref.value[node] = np.array(
                [stats[1] / max(stats[0], 1e-12)], np.float32)
        return cnt

    def grow(frontier, depth):
        """One level: frontier = [(node id, row mask)] in leaf order."""
        if not frontier:
            return
        counts = [node_value(node, rows) for node, rows in frontier]
        if depth >= params.max_depth:
            return
        cand = _np_candidates(seed, tree_idx, depth, len(frontier), m,
                              m_prime)
        next_frontier = []
        for h, (node, rows) in enumerate(frontier):
            if counts[h] < 2 * params.min_records:
                continue
            best_g, best_j, best_t = -np.inf, None, 0.0
            for j in range(m):
                if not cand[h, j]:
                    continue
                g, t = oracle_numeric(num[rows, j], y[rows], w[rows], C,
                                      imp, task, params.min_records)
                if g > best_g:                     # first feature wins ties
                    best_g, best_j, best_t = g, j, t
            if best_j is None or not np.isfinite(best_g) or best_g <= 1e-9:
                continue
            # the engines compute tau = (a + v) * 0.5 in float32
            iv = np.sort(num[rows & (w > 0), best_j].astype(np.float32))
            lo = iv[iv <= best_t].max()
            hi = iv[iv > best_t].min()
            thr = (lo + hi) * np.float32(0.5)
            ref.feature[node] = best_j
            ref.gain[node] = float(best_g)
            ref.threshold[node] = thr
            lc = ref.new_node(depth + 1)
            rc = ref.new_node(depth + 1)
            ref.children[node] = [lc, rc]
            next_frontier.append((lc, rows & (num[:, best_j] <= thr)))
            next_frontier.append((rc, rows & (num[:, best_j] > thr)))
        grow(next_frontier, depth + 1)

    grow([(root, np.ones(n, bool))], 0)
    return ref


def _fitted_tree(num, y, params, seed, tree_idx, task):
    from repro.core import presort, tree as tree_lib
    from repro.core.dataset import from_numpy
    ds = from_numpy(num, None, y,
                    task="regression" if task == "regression" else
                    "classification")
    si = presort.presort_columns(ds.num)
    sv = presort.gather_sorted(ds.num, si)
    tr, _ = tree_lib.build_tree(
        num=ds.num, cat=ds.cat, labels=ds.labels, sorted_vals=sv,
        sorted_idx=si, arities=ds.arities, num_classes=ds.num_classes,
        params=params, seed=seed, tree_idx=tree_idx)
    return tr, ds.num_classes


def _assert_tree_matches_oracle(tr, ref, task, ctx):
    assert tr.num_nodes == len(ref.feature), ctx
    np.testing.assert_array_equal(tr.feature, ref.feature, err_msg=ctx)
    np.testing.assert_array_equal(tr.children, ref.children, err_msg=ctx)
    np.testing.assert_array_equal(tr.depth, ref.depth, err_msg=ctx)
    np.testing.assert_array_equal(tr.threshold,
                                  np.asarray(ref.threshold, np.float32),
                                  err_msg=ctx)
    np.testing.assert_allclose(tr.gain, ref.gain, rtol=1e-4, atol=1e-4,
                               err_msg=ctx)
    np.testing.assert_allclose(tr.n_node, ref.n_node, rtol=0, atol=0,
                               err_msg=ctx)
    np.testing.assert_allclose(tr.value, np.stack(ref.value),
                               rtol=1e-6, atol=1e-6, err_msg=ctx)


@pytest.mark.parametrize("backend", ["segment", "scan"])
@pytest.mark.parametrize("seed", [0, 4])
def test_whole_tree_matches_recursive_oracle_classification(backend, seed):
    """Node-for-node equality of build_tree against the recursive numpy
    reference on a small continuous classification dataset."""
    from repro.core import tree as tree_lib
    rng = np.random.default_rng(seed)
    n, m, C = 400, 5, 3
    num = rng.normal(size=(n, m)).astype(np.float32)
    y = np.digitize(num[:, 0] + 0.7 * num[:, 1],
                    [-0.5, 0.5]).astype(np.int32)
    params = tree_lib.TreeParams(max_depth=4, min_records=3,
                                 backend=backend)
    tr, C_ds = _fitted_tree(num, y, params, seed=11, tree_idx=seed,
                            task="classification")
    ref = build_tree_oracle(num, y, params, seed=11, tree_idx=seed, C=C_ds)
    _assert_tree_matches_oracle(tr, ref, "classification",
                                f"{backend}/seed{seed}")


def test_whole_tree_matches_recursive_oracle_regression():
    from repro.core import tree as tree_lib
    rng = np.random.default_rng(2)
    n, m = 350, 4
    num = rng.normal(size=(n, m)).astype(np.float32)
    y = (2 * num[:, 0] + num[:, 1] ** 2
         + 0.1 * rng.normal(size=n)).astype(np.float32)
    params = tree_lib.TreeParams(max_depth=4, min_records=4,
                                 impurity="variance", task="regression",
                                 bagging="none")
    tr, _ = _fitted_tree(num, y, params, seed=5, tree_idx=0,
                         task="regression")
    ref = build_tree_oracle(num, y, params, seed=5, tree_idx=0, C=2)
    # float32 device sums vs float64 numpy sums: structure exact, float
    # leaf statistics to tolerance
    assert tr.num_nodes == len(ref.feature)
    np.testing.assert_array_equal(tr.feature, ref.feature)
    np.testing.assert_array_equal(tr.children, ref.children)
    np.testing.assert_array_equal(tr.depth, ref.depth)
    np.testing.assert_allclose(tr.threshold, ref.threshold,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(tr.gain, ref.gain, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(tr.value, np.stack(ref.value),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Hypothesis sweep (pytest -m hypothesis; fixed profile in conftest.py)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @pytest.mark.hypothesis
    @given(st.integers(0, 10_000),
           st.sampled_from(ALL_ENGINES),
           st.sampled_from(["gini", "entropy"]),
           st.sampled_from([1.0, 4.0]))
    def test_property_numeric_engines_match_oracle(seed, backend, impurity,
                                                   min_records):
        check_against_oracle(backend, seed, impurity, min_records)
