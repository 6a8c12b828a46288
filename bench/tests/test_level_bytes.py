"""The level step's least-bytes count on hand-counted shapes."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import roofline  # noqa: E402


def test_hist_level_by_hand():
    # m = 28 -> m' = 6; one level: 3 splittable leaves, 1000 in-bag rows
    got = roofline.level_bytes([(3, 1000)], m=28, mode="hist",
                               num_bins=255, num_classes=2)
    rows = 1000 * (6 * 1 + 12)                 # bucket ids + leaf/weight
    tables = 3 * 6 * 255 * 2 * 4               # f32 (bucket x stat)
    assert got == rows + tables == 18000 + 36720


def test_exact_level_by_hand():
    # m = 54 -> m' = 8; two levels
    got = roofline.level_bytes([(1, 500), (2, 400)], m=54, mode="exact",
                               num_bins=255, num_classes=7)
    assert got == 500 * (8 * 4 + 12) + 400 * (8 * 4 + 12) == 39600


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
    got = roofline.level_bytes([(L, n)], m=m, mode=mode, num_bins=B,
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
    lv = roofline.tree_levels(Stump, X, seed=3, tree_idx=0, max_depth=4)
    w = __import__("reference").bag_weights(3, 0, 64)
    left = int(((X[:, 0] <= 0.5) & (w > 0)).sum())
    # the root, then only the left child holds >= 2 in-bag weight
    assert lv == [(1, int((w > 0).sum())), (1, left)]
