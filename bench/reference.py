"""Plain references of what the benchmark's timed paths produce.

Written from the paper's algorithm (arXiv 1804.06755, Alg. 1-2) and the
library's documented semantics, in numpy, with nothing imported from the
program under test:

* a random-forest tree builder and checker, level by level, for both
  split modes: exact (every midpoint between consecutive distinct in-bag
  values of a candidate column) and hist (every cut between equi-depth
  quantile buckets, `x <= edges[b]`);
* categorical columns in either mode (Breiman's ordering, paper §2.4):
  at a node, the categories present among its in-bag rows are ordered by
  P(last class | v), ties by category id, and every prefix of that order
  is a candidate left set; a row goes left iff its category is in the
  node's left set.  Categories are counted with `np.unique` over the
  node's rows, never with an array of the column's arity, which may
  reach 10^7;
* forest descent (`x <= threshold` goes left, leaves average).

Columns are numbered as the library numbers them: numeric columns
0..m_num-1, then categorical columns m_num..m-1.

The seeded draws are part of the specification (paper §2.2: every worker
derives the same draw from (seed, tree)): per-row bag counts are
Poisson(1) from `fold_in(PRNGKey(seed), tree)`, and the candidate columns
of leaf h at depth d are the top m' of `m` uniforms from
`fold_in(fold_in(fold_in(PRNGKey(seed ^ 0x5EED), tree), d), h)`.  They are
drawn here with `jax.random`, the generator the specification names.

`check_tree` walks a trained tree level by level over the data, routing
rows by the tree's own thresholds, and at every node recomputes in
float64 the in-bag class totals and the best split over the node's
candidate columns.  It reports:

* `gain_gap`: the widest gap, over the nodes, between the best gain and
  the gain of the split the tree holds (0 for a leaf that should not
  split), per unit of in-bag weight at the node;
* `node_errors`: nodes whose weight or class distribution differs from
  the routed rows, splits at nodes that may not split, splits on a
  column that is not a candidate or at a threshold that is no candidate
  cut (a categorical left set that leaves a side under `min_records`),
  left sets holding ids outside [0, arity), and nodes the walk never
  reaches.

A trained tree's categorical left sets are read through `cat_left`.

`build_tree` grows the reference's own tree with gains computed in a
given precision: float32 follows the program node for node; bfloat16 is
the control that a comparison has to reject.
"""
from __future__ import annotations

import functools
import math
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

SPLIT_EPS = 1e-9        # a leaf splits only on a gain above this
THREADS = min(8, os.cpu_count() or 1)   # columns searched side by side


# ---------------------------------------------------------------------------
# Seeded draws (the specification's generator)
# ---------------------------------------------------------------------------

def m_prime(m: int) -> int:
    """Candidate columns per node: ceil(sqrt(m)) (paper §2.4 default)."""
    r = math.isqrt(m)
    return max(1, r + (0 if r * r == m else 1))


@functools.lru_cache(maxsize=None)
def _jax_draws():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("n",))
    def bag(seed, tree, n):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), tree)
        return jax.random.poisson(key, 1.0, (n,))

    @functools.partial(jax.jit, static_argnames=("num_leaves", "m"))
    def uniforms(seed, tree, depth, num_leaves, m):
        key = jax.random.fold_in(jax.random.PRNGKey(seed ^ 0x5EED), tree)
        key = jax.random.fold_in(key, depth)
        keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            key, jnp.arange(num_leaves))
        return jax.vmap(lambda k: jax.random.uniform(k, (m,)))(keys)

    return bag, uniforms


def bag_weights(seed: int, tree: int, n: int) -> np.ndarray:
    """(n,) float64 Poisson(1) bag counts of one tree."""
    bag, _ = _jax_draws()
    return np.asarray(bag(seed, tree, n)).astype(np.float64)


def candidates(seed: int, tree: int, depth: int, num_leaves: int, m: int,
               k: int) -> np.ndarray:
    """(num_leaves, m) bool: the top k of m uniforms per leaf (first
    index first among equal draws)."""
    _, uniforms = _jax_draws()
    # each leaf's draw depends on its own index only, so drawing for a
    # power-of-two count bounds the compiles to one per doubling
    width = max(8, 1 << (num_leaves - 1).bit_length())
    g = np.asarray(uniforms(seed, tree, depth, width, m))[:num_leaves]
    top = np.argsort(-g, axis=1, kind="stable")[:, :k]
    mask = np.zeros((num_leaves, m), bool)
    np.put_along_axis(mask, top, True, axis=1)
    return mask


# ---------------------------------------------------------------------------
# Buckets (hist mode)
# ---------------------------------------------------------------------------

def quantize_edges(X: np.ndarray, num_bins: int) -> np.ndarray:
    """(m, B) float32: edges[j, b] is the value at sorted position
    (b+1)·n//B − 1 of column j, the largest value of bucket b."""
    n = X.shape[0]
    pos = np.clip((np.arange(1, num_bins + 1) * n) // num_bins - 1, 0, n - 1)
    return np.stack([np.sort(X[:, j])[pos] for j in range(X.shape[1])])


def bin_columns(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """(n, m) bucket ids: the number of lower edges strictly below x."""
    return np.stack([np.searchsorted(edges[j, :-1], X[:, j], side="left")
                     for j in range(X.shape[1])], axis=1).astype(np.int32)


# ---------------------------------------------------------------------------
# Gains
# ---------------------------------------------------------------------------

def _gain(left: np.ndarray, right: np.ndarray, dtype) -> np.ndarray:
    """Gini gain N·gini(parent) − N·gini(left) − N·gini(right), stats on
    the last axis, each step rounded to `dtype`."""
    left = left.astype(dtype)
    right = right.astype(dtype)

    def wi(h):
        n = h.sum(-1, dtype=dtype)
        sq = (h * h).sum(-1, dtype=dtype)
        return n - np.where(n > 0, sq / np.maximum(n, dtype(1e-12)),
                            dtype(0))
    return (wi(left + right) - wi(left) - wi(right)).astype(np.float64)


def _seg_first_max(vals, seg, num_segments):
    """Per segment: the max of `vals` and the index of its first hit
    (`seg` ascending).  Empty segments give (-inf, -1)."""
    best = np.full(num_segments, -np.inf)
    np.maximum.at(best, seg, vals)
    hit = np.flatnonzero((vals == best[seg]) & np.isfinite(vals))
    first = np.full(num_segments, -1)
    s, i = np.unique(seg[hit], return_index=True)
    first[s] = hit[i]
    return best, first


# ---------------------------------------------------------------------------
# One level's search
# ---------------------------------------------------------------------------

class Data:
    """Rows, labels and (hist) bucket ids, shared by the trees checked.

    `cat` (n, m_cat) holds the categorical columns' ids and `arities`
    their published cardinalities (ids lie in [0, arity))."""

    def __init__(self, X, y, num_classes, mode, num_bins=255, cat=None,
                 arities=()):
        self.X = np.ascontiguousarray(X, np.float32)
        self.y = np.asarray(y, np.int64)
        self.n, self.m_num = self.X.shape
        self.cat = (np.zeros((self.n, 0), np.int64) if cat is None
                    else np.asarray(cat, np.int64))
        self.arities = np.asarray(arities, np.int64)
        if self.arities.shape != (self.cat.shape[1],):
            raise ValueError("one arity per categorical column")
        self.m = self.m_num + self.cat.shape[1]
        self.C = num_classes
        self.mode = mode
        if mode == "hist":
            if self.m_num:
                self.edges = quantize_edges(self.X, num_bins)
                self.bins = bin_columns(self.X, self.edges)
        elif mode != "exact":
            raise ValueError(f"unknown split mode {mode!r}")


def _search_feature(data, f, rows, pos, w, totals, leaves, min_records,
                    dtype, prog_feat, prog_thr):
    """Best split of column f for each leaf in `leaves` (the leaves that
    have f as a candidate; `rows` are their in-bag rows).

    Returns (best gain, its threshold, gain of the held split), each
    (len(leaves),); -inf where f gives no valid cut.
    """
    k = len(leaves)
    local = np.full(totals.shape[0], -1)
    local[leaves] = np.arange(k)
    p = local[pos[rows]]
    c = data.y[rows]
    ww = w[rows]
    tot = totals[leaves]
    mine_leaf = prog_feat[leaves] == f
    if data.mode == "hist":
        B = data.edges.shape[1]
        tab = np.bincount((p * B + data.bins[rows, f]) * data.C + c,
                          weights=ww, minlength=k * B * data.C
                          ).reshape(k, B, data.C)
        left = np.cumsum(tab, axis=1)[:, :-1, :]            # cuts 0..B-2
        right = tot[:, None, :] - left
        ok = (left.sum(2) >= min_records) & (right.sum(2) >= min_records)
        gain = np.where(ok, _gain(left, right, dtype), -np.inf)  # (k, B-1)
        at = gain.argmax(axis=1)
        best = gain[np.arange(k), at]
        thr = data.edges[f, at]
        mine = mine_leaf[:, None] & (data.edges[f, :-1][None, :]
                                     == prog_thr[leaves][:, None])
        held = np.where(mine, gain, -np.inf).max(axis=1, initial=-np.inf)
        return best, thr, held
    x = data.X[rows, f]
    order = np.lexsort((x, p))
    p, c, ww, x = p[order], c[order], ww[order], x[order]
    n = len(p)
    cw = np.zeros((n, data.C))
    cw[np.arange(n), c] = ww
    cum = np.cumsum(cw, axis=0)
    start = np.r_[True, p[1:] != p[:-1]]
    first = np.maximum.accumulate(np.where(start, np.arange(n), 0))
    within = cum - (cum[first] - cw[first])     # prefix inside each leaf
    cut = np.flatnonzero((p[:-1] == p[1:]) & (x[:-1] < x[1:]))
    if cut.size == 0:
        none = np.full(k, -np.inf)
        return none, np.zeros(k, np.float32), none
    seg = p[cut]
    left = within[cut]
    right = tot[seg] - left
    thr = (x[cut + 1] + x[cut]) * np.float32(0.5)
    ok = (left.sum(1) >= min_records) & (right.sum(1) >= min_records)
    gain = np.where(ok, _gain(left, right, dtype), -np.inf)
    best, at = _seg_first_max(gain, seg, k)
    thr_best = np.where(at >= 0, thr[np.maximum(at, 0)], np.float32(0))
    held = np.full(k, -np.inf)
    mine = mine_leaf[seg] & (thr == prog_thr[leaves][seg])
    np.maximum.at(held, seg[mine], gain[mine])
    return best, thr_best, held


def _search_categorical(data, f, rows, pos, w, totals, leaves,
                        min_records, dtype, prog_feat, prog_left):
    """Best left set of categorical column f for each leaf in `leaves`.

    Returns (best gain, its left set (a sorted id array per leaf), gain
    of the held split), the gains (len(leaves),); -inf where f gives no
    valid cut or the held set is none.  Ordering metric and gains are in
    float64 for the check and in float32 otherwise, as the library
    computes them.
    """
    k = len(leaves)
    j = f - data.m_num
    local = np.full(totals.shape[0], -1)
    local[leaves] = np.arange(k)
    p = local[pos[rows]]
    v = data.cat[rows, j]
    # (leaf, category) groups in (leaf, id) order, class weights summed
    key, inv = np.unique(p * data.arities[j] + v, return_inverse=True)
    tab = np.bincount(inv.reshape(-1) * data.C + data.y[rows],
                      weights=w[rows], minlength=len(key) * data.C
                      ).reshape(len(key), data.C)
    g_leaf, g_cat = key // data.arities[j], key % data.arities[j]
    tot = totals[leaves]
    mdt = np.float64 if dtype == np.float64 else np.float32
    count = tab.sum(1)
    metric = tab[:, -1].astype(mdt) / count.astype(mdt)
    order = np.lexsort((g_cat, metric, g_leaf))
    g_leaf, g_cat, tab = g_leaf[order], g_cat[order], tab[order]
    U = len(key)
    cum = np.cumsum(tab, axis=0)
    start = np.r_[True, g_leaf[1:] != g_leaf[:-1]]
    first = np.maximum.accumulate(np.where(start, np.arange(U), 0))
    within = cum - (cum[first] - tab[first])    # prefix inside each leaf
    cut = np.flatnonzero(g_leaf[:-1] == g_leaf[1:])   # cut after cut[i]
    best = np.full(k, -np.inf)
    lefts = {}
    if cut.size:
        seg = g_leaf[cut]
        left = within[cut]
        right = tot[seg] - left
        ok = (left.sum(1) >= min_records) & (right.sum(1) >= min_records)
        gain = np.where(ok, _gain(left, right, dtype), -np.inf)
        best, at = _seg_first_max(gain, seg, k)
        for h in np.flatnonzero(at >= 0):
            c = cut[at[h]]
            lefts[int(h)] = np.sort(g_cat[first[c]:c + 1])
    held = np.full(k, -np.inf)
    mine = np.flatnonzero(prog_feat[leaves] == f)
    if mine.size:
        ids = [np.asarray(prog_left[int(leaves[h])], np.int64) for h in mine]
        held_keys = np.concatenate(
            [h * data.arities[j] + i[(i >= 0) & (i < data.arities[j])]
             for h, i in zip(mine, ids)])
        goes = np.isin(g_leaf * data.arities[j] + g_cat, held_keys)
        left = np.zeros((k, data.C))
        np.add.at(left, g_leaf[goes], tab[goes])
        left, right = left[mine], tot[mine] - left[mine]
        ok = (left.sum(1) >= min_records) & (right.sum(1) >= min_records)
        held[mine] = np.where(ok, _gain(left, right, dtype), -np.inf)
    return best, lefts, held


def search_level(data, pos, w, cand, totals, min_records, dtype,
                 prog_feat=None, prog_thr=None, prog_left=None):
    """Best split of every leaf of one level over its candidate columns.

    pos (n,): leaf index of each row, -1 outside the level's open leaves;
    cand (L, m) bool; totals (L, C) in-bag class weights.  The first
    column and, within it, the first cut win ties.  Returns (gain,
    feature, threshold, left sets {leaf: sorted ids} of the leaves whose
    best column is categorical, gain of the held split (feature
    prog_feat[h] at threshold prog_thr[h] or left set prog_left[h]; -inf
    where it is no valid candidate cut)).
    """
    L = totals.shape[0]
    if prog_feat is None:
        prog_feat = np.full(L, -1)
        prog_thr = np.zeros(L, np.float32)
    best = np.full(L, -np.inf)
    feat = np.full(L, -1)
    thr = np.zeros(L, np.float32)
    lefts = {}
    held = np.full(L, -np.inf)
    inbag = (w > 0) & (pos >= 0)

    def column(f):
        leaves = np.flatnonzero(cand[:, f])
        if leaves.size == 0:
            return None
        rows = np.flatnonzero(inbag & cand[np.maximum(pos, 0), f])
        if f < data.m_num:
            g, t, h = _search_feature(data, f, rows, pos, w, totals, leaves,
                                      min_records, dtype, prog_feat,
                                      prog_thr)
            return leaves, g, t, {}, h
        g, sets, h = _search_categorical(data, f, rows, pos, w, totals,
                                         leaves, min_records, dtype,
                                         prog_feat, prog_left)
        return leaves, g, np.zeros(len(leaves), np.float32), sets, h

    # columns are searched side by side (numpy's sorts and scans release
    # the GIL) and merged in column order, so the result is the same
    with ThreadPoolExecutor(THREADS) as pool:
        found = list(pool.map(column, range(data.m)))
    for f, res in enumerate(found):
        if res is None:
            continue
        leaves, g, t, sets, h = res
        better = g > best[leaves]
        best[leaves] = np.where(better, g, best[leaves])
        feat[leaves] = np.where(better, f, feat[leaves])
        thr[leaves] = np.where(better, t, thr[leaves])
        for i in np.flatnonzero(better):
            if f < data.m_num:
                lefts.pop(int(leaves[i]), None)
            else:
                lefts[int(leaves[i])] = sets[int(i)]
        held[leaves] = np.maximum(held[leaves], h)
    return best, feat, thr, lefts, held


# ---------------------------------------------------------------------------
# Trees
# ---------------------------------------------------------------------------

def _level_state(data, node_of, nodes, w):
    """Leaf index per row and in-bag class totals for the level's nodes."""
    index = np.full(int(node_of.max()) + 1, -1)
    index[nodes] = np.arange(len(nodes))
    pos = index[node_of]
    inb = (w > 0) & (pos >= 0)
    totals = np.bincount(pos[inb] * data.C + data.y[inb], weights=w[inb],
                         minlength=len(nodes) * data.C
                         ).reshape(len(nodes), data.C)
    return pos, totals


def cat_left(tree, node: int) -> np.ndarray:
    """Sorted int32 ids of the categories a trained tree's categorical
    node sends left: `tree.cat_left(node)` where the tree has that
    accessor, else the ids set in its dense `(N, max_arity)` mask."""
    if isinstance(tree, dict):
        return np.asarray(tree["cat_left"][int(node)], np.int32)
    if hasattr(tree, "cat_left"):
        return np.asarray(tree.cat_left(int(node)), np.int32)
    return np.flatnonzero(tree.cat_mask[int(node)]).astype(np.int32)


CAT_KEY_SHIFT = 31      # a (node, category) key: node << 31 | id


def tree_arrays(tree, m_num: int) -> dict:
    """The flat arrays of a trained tree (the program's `Tree`, or
    `build_tree`'s dict), with the left set of each categorical node
    (feature >= m_num) and their (node, id) keys for routing."""
    get = (tree.get if isinstance(tree, dict)
           else lambda k: getattr(tree, k))
    t = {"feature": np.asarray(get("feature")),
         "threshold": np.asarray(get("threshold"), np.float32),
         "children": np.asarray(get("children")),
         "value": np.asarray(get("value"), np.float64),
         "n_node": np.asarray(get("n_node"), np.float64),
         "depth": np.asarray(get("depth")), "m_num": m_num}
    nodes = np.flatnonzero(t["feature"] >= m_num)
    t["cat_left"] = {int(nd): cat_left(tree, nd) for nd in nodes}
    keys = [(np.int64(nd) << CAT_KEY_SHIFT) + ids[ids >= 0].astype(np.int64)
            for nd, ids in t["cat_left"].items()]
    t["cat_keys"] = np.sort(np.concatenate(keys)) if keys else \
        np.zeros(0, np.int64)
    return t


def go_left(t: dict, data, rows: np.ndarray, nd: np.ndarray) -> np.ndarray:
    """Whether each row goes left at its split node `nd`: `x <= threshold`
    at a numeric node, its category in the left set at a categorical
    one."""
    f = t["feature"][nd]
    num = f < t["m_num"]
    left = np.zeros(len(rows), bool)
    left[num] = data.X[rows[num], f[num]] <= t["threshold"][nd[num]]
    if not num.all():
        r, n_c = rows[~num], nd[~num]
        ids = data.cat[r, f[~num] - t["m_num"]]
        left[~num] = np.isin((n_c.astype(np.int64) << CAT_KEY_SHIFT) + ids,
                             t["cat_keys"])
    return left


def walk(t: dict, data, max_depth: int):
    """Yield (depth, node ids, node of each row) level by level, routing
    the rows of each split node by the tree's own condition (`go_left`)
    after each level."""
    node_of = np.zeros(data.n, np.int64)
    nodes = np.array([0])
    for depth in range(max_depth + 1):
        yield depth, nodes, node_of
        split_nodes = nodes[t["feature"][nodes] >= 0]
        if split_nodes.size == 0:
            return
        sel = np.flatnonzero(np.isin(node_of, split_nodes))
        nd = node_of[sel]
        node_of[sel] = np.where(go_left(t, data, sel, nd),
                                t["children"][nd, 0], t["children"][nd, 1])
        nodes = np.sort(t["children"][split_nodes].reshape(-1))


def check_tree(tree, data, seed, tree_idx, *, max_depth, min_records=1.0):
    """Walk a trained tree over the data; see the module docstring.
    Returns {"gain_gap", "node_errors", "nodes"}."""
    t = tree_arrays(tree, data.m_num)
    w = bag_weights(seed, tree_idx, data.n)
    k = m_prime(data.m)
    gap, seen = 0.0, 0
    errors = {}                     # kind -> [count, first (depth, node)]

    def flag(kind, bad, depth, nodes):
        if bad.any():
            rec = errors.setdefault(kind, [0, (depth, int(nodes[bad][0]))])
            rec[0] += int(bad.sum())

    for depth, nodes, node_of in walk(t, data, max_depth):
        seen += len(nodes)
        flag("depth", t["depth"][nodes] != depth, depth, nodes)
        pos, totals = _level_state(data, node_of, nodes, w)
        count = totals.sum(1)
        flag("weight", t["n_node"][nodes] != count, depth, nodes)
        C = min(data.C, t["value"].shape[1])
        flag("value", (np.rint(t["value"][nodes, :C] * count[:, None])
                       != totals[:, :C]).any(1), depth, nodes)
        splits = t["feature"][nodes] >= 0
        splittable = (count >= 2 * min_records) & (depth < max_depth)
        flag("split_not_allowed", splits & ~splittable, depth, nodes)
        if not splittable.any():
            continue
        cand = candidates(seed, tree_idx, depth, len(nodes), data.m, k)
        cand &= splittable[:, None]
        held_left = {h: t["cat_left"][int(nd)] for h, nd in enumerate(nodes)
                     if int(nd) in t["cat_left"]}
        out_of_range = np.zeros(len(nodes), bool)
        for h, ids in held_left.items():
            arity = data.arities[t["feature"][nodes[h]] - data.m_num]
            out_of_range[h] = ((ids < 0) | (ids >= arity)).any()
        flag("cat_id_out_of_range", out_of_range, depth, nodes)
        best, _, _, _, held = search_level(
            data, pos, w, cand, totals, min_records, np.float64,
            np.where(splits, t["feature"][nodes], -1), t["threshold"][nodes],
            held_left)
        best_ok = np.where(np.isfinite(best), best, 0.0)
        bad = splits & ~np.isfinite(held)
        flag("no_candidate_cut", bad, depth, nodes)
        lost = np.where(splits, best_ok - np.where(bad, best_ok, held),
                        np.maximum(best_ok, 0.0))
        lost = np.where(splittable, lost, 0.0) / np.maximum(count, 1.0)
        gap = max(gap, float(lost.max()))
    flag("unreachable", np.array([len(t["feature"]) > seen]), -1,
         np.array([seen]))
    return {"gain_gap": gap,
            "node_errors": sum(c for c, _ in errors.values()),
            "nodes": seen,
            "errors": {kind: {"count": c, "first_depth": d, "first_node": n}
                       for kind, (c, (d, n)) in errors.items()}}


def build_tree(data, seed, tree_idx, *, max_depth, min_records=1.0,
               dtype=np.float32):
    """Grow the reference's own tree with gains rounded to `dtype`.

    Nodes are numbered as the paper's level-wise builder numbers them:
    each level's children in the order of their parents, left first.
    Returns a dict of flat arrays (the fields `check_tree` reads).
    """
    w = bag_weights(seed, tree_idx, data.n)
    k = m_prime(data.m)
    feature, threshold, children, value, n_node, depth_of = \
        [], [], [], [], [], []
    lefts = {}

    def new_node(d):
        feature.append(-1)
        threshold.append(np.float32(0))
        children.append([-1, -1])
        value.append(np.zeros(data.C, np.float32))
        n_node.append(0.0)
        depth_of.append(d)
        return len(feature) - 1

    node_of = np.zeros(data.n, np.int64)
    nodes = np.array([new_node(0)])
    for depth in range(max_depth + 1):
        if nodes.size == 0:
            break
        pos, totals = _level_state(data, node_of, nodes, w)
        count = totals.sum(1)
        for h, nd in enumerate(nodes):
            n_node[nd] = count[h]
            value[nd] = (totals[h].astype(np.float32)
                         / np.float32(max(count[h], 1e-12)))
        splittable = (count >= 2 * min_records) & (depth < max_depth)
        if not splittable.any():
            break
        cand = candidates(seed, tree_idx, depth, len(nodes), data.m, k)
        cand &= splittable[:, None]
        best, feat, thr, left_sets, _ = search_level(
            data, pos, w, cand, totals, min_records, dtype)
        split = splittable & np.isfinite(best) & (best > SPLIT_EPS)
        kids = []
        for h in np.flatnonzero(split):
            nd = nodes[h]
            feature[nd], threshold[nd] = int(feat[h]), thr[h]
            if feat[h] >= data.m_num:
                lefts[int(nd)] = left_sets[int(h)]
            children[nd] = [new_node(depth + 1), new_node(depth + 1)]
            kids.extend(children[nd])
        if not kids:
            break
        split_nodes = nodes[split]
        sel = np.flatnonzero(np.isin(node_of, split_nodes))
        nd = node_of[sel]
        t = tree_arrays({"feature": feature, "threshold": threshold,
                         "children": children, "value": value,
                         "n_node": n_node, "depth": depth_of,
                         "cat_left": lefts}, data.m_num)
        node_of[sel] = np.where(go_left(t, data, sel, nd),
                                t["children"][nd, 0], t["children"][nd, 1])
        nodes = np.asarray(kids)
    return {"feature": np.asarray(feature, np.int32),
            "threshold": np.asarray(threshold, np.float32),
            "children": np.asarray(children, np.int32),
            "value": np.stack(value).astype(np.float32),
            "n_node": np.asarray(n_node, np.float32),
            "depth": np.asarray(depth_of, np.int32),
            "cat_left": lefts}


# ---------------------------------------------------------------------------
# Forest descent
# ---------------------------------------------------------------------------

def forest_proba(forest: dict, X: np.ndarray) -> np.ndarray:
    """(B, C) float64 mean over trees of the leaf distributions reached by
    `x <= threshold` -> left.  `forest` holds (T, N) feature / threshold,
    (T, N, 2) children and (T, N, C) value arrays."""
    X = np.asarray(X, np.float32)
    T = forest["feature"].shape[0]
    rows = np.arange(X.shape[0])
    out = np.zeros((X.shape[0], forest["value"].shape[2]))
    for t in range(T):
        feat, thr = forest["feature"][t], forest["threshold"][t]
        kids = forest["children"][t]
        node = np.zeros(X.shape[0], np.int64)
        while True:
            f = feat[node]
            inner = f >= 0
            if not inner.any():
                break
            go_left = X[rows, np.maximum(f, 0)] <= thr[node]
            nxt = np.where(go_left, kids[node, 0], kids[node, 1])
            node = np.where(inner, nxt, node)
        out += forest["value"][t][node].astype(np.float64)
    return out / T
