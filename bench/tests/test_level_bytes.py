"""The level step's least-bytes count on hand-counted shapes."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import reference  # noqa: E402
import roofline  # noqa: E402


def numeric(leaves, rows, k):
    """A level of a numeric-only job: every leaf draws k numeric
    candidates."""
    return roofline.Level(rows=rows, num_reads=rows * k, cat_reads=0,
                          num_tables=leaves * k, cat_entries=0)


def test_hist_level_by_hand():
    # m = 28 -> m' = 6; one level: 3 splittable leaves, 1000 in-bag rows
    got = roofline.level_bytes([numeric(3, 1000, 6)], mode="hist",
                               num_bins=255, num_classes=2)
    rows = 1000 * (6 * 1 + 12)                 # bucket ids + leaf/weight
    tables = 3 * 6 * 255 * 2 * 4               # f32 (bucket x stat)
    assert got == rows + tables == 18000 + 36720


def test_exact_level_by_hand():
    # m = 54 -> m' = 8; two levels
    got = roofline.level_bytes([numeric(1, 500, 8), numeric(2, 400, 8)],
                               mode="exact", num_bins=255, num_classes=7)
    assert got == 500 * (8 * 4 + 12) + 400 * (8 * 4 + 12) == 39600


@pytest.mark.parametrize("mode,value", [("exact", 4), ("hist", 1)])
def test_categorical_level_by_hand(mode, value):
    # 1000 rows with 2 numeric and 1 categorical candidate, 200 with 3
    # numeric; 2 leaves, 2 numeric tables in one and 3 in the other; the
    # categorical candidate meets 37 categories: 37 stat vectors of S = 2
    lv = roofline.Level(rows=1200, num_reads=1000 * 2 + 200 * 3,
                        cat_reads=1000, num_tables=5, cat_entries=37)
    got = roofline.level_bytes([lv], mode=mode, num_bins=255, num_classes=2)
    want = 2600 * value + 1000 * 4 + 1200 * 12 + 37 * 2 * 4
    if mode == "hist":
        want += 5 * 255 * 2 * 4
    assert got == want


@pytest.mark.parametrize("mode", ["hist", "exact"])
@pytest.mark.parametrize("n,m,S,L", [(4096, 28, 2, 64), (1000, 54, 7, 512),
                                     (1 << 20, 28, 2, 2048)])
def test_bound_never_exceeds_what_the_level_holds(mode, n, m, S, L):
    """At most every row in an open leaf, every column, one table per
    column of every padded leaf: what the level's inputs and tables hold."""
    B = 255
    value = 1 if mode == "hist" else 4
    held = n * m * value + n * 4 * 3               # values, leaf ids, weights
    held += n * 4                                  # new leaf ids
    if mode == "hist":
        held += (L + 1) * m * B * S * 4            # the level's tables
    k = reference.m_prime(m)
    got = roofline.level_bytes([numeric(L, n, k)], mode=mode, num_bins=B,
                               num_classes=S)
    assert 0 < got <= held


def test_tree_levels_counts_inbag_rows_of_splittable_leaves():
    class Stump:                  # root split on column 0 at 0.5, depth 1
        feature = np.array([0, -1, -1])
        threshold = np.array([0.5, 0, 0], np.float32)
        children = np.array([[1, 2], [-1, -1], [-1, -1]])
        n_node = np.array([10.0, 6.0, 1.0])
        value = np.zeros((3, 2))
        depth = np.array([0, 1, 1])
    X = np.linspace(0, 1, 64, dtype=np.float32)[:, None]
    data = reference.Data(X, np.zeros(64), 2, "exact")
    lv = roofline.tree_levels(Stump, data, seed=3, tree_idx=0, max_depth=4)
    w = reference.bag_weights(3, 0, 64)
    left = int(((X[:, 0] <= 0.5) & (w > 0)).sum())
    # the root, then only the left child holds >= 2 in-bag weight; one
    # column, so each leaf's one candidate is it
    assert lv == [numeric(1, int((w > 0).sum()), 1), numeric(1, left, 1)]


def test_tree_levels_counts_categories_present_per_leaf():
    """A root split on the categorical column: left {2, 5}.  The levels'
    reads follow the drawn candidates, and each categorical candidate of
    a leaf adds the distinct ids among its in-bag rows."""
    n = 4000
    rng = np.random.default_rng(0)
    X = rng.random((n, 3)).astype(np.float32)
    cat = rng.choice(np.array([2, 5, 9, 70000]), n)[:, None]
    data = reference.Data(X, np.zeros(n), 2, "exact", cat=cat,
                          arities=[1 << 17])
    stump = {"feature": np.array([3, -1, -1]),
             "threshold": np.zeros(3, np.float32),
             "children": np.array([[1, 2], [-1, -1], [-1, -1]]),
             "n_node": np.array([10.0, 5.0, 5.0]),
             "value": np.zeros((3, 2)), "depth": np.array([0, 1, 1]),
             "cat_left": {0: np.array([2, 5])}}
    lv = roofline.tree_levels(stump, data, seed=5, tree_idx=1, max_depth=3)
    w = reference.bag_weights(5, 1, n) > 0
    k = reference.m_prime(4)
    rows_of = [w, w & np.isin(cat[:, 0], [2, 5]),
               w & ~np.isin(cat[:, 0], [2, 5])]
    want = []
    for depth, leaves in ((0, [0]), (1, [1, 2])):
        cand = reference.candidates(5, 1, depth, len(leaves), 4, k)
        rows = [int(rows_of[h].sum()) for h in leaves]
        want.append(roofline.Level(
            rows=sum(rows),
            num_reads=sum(r * int(c[:3].sum()) for r, c in zip(rows, cand)),
            cat_reads=sum(r * int(c[3]) for r, c in zip(rows, cand)),
            num_tables=int(cand[:, :3].sum()),
            cat_entries=sum(len(np.unique(cat[rows_of[h], 0]))
                            for h, c in zip(leaves, cand) if c[3])))
    assert lv == want
    assert sum(x.cat_reads for x in lv) > 0      # the draw does hit it
