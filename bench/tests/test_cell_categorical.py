"""Categorical columns in a training cell: a configuration that returns
(num, cat, y) and names its arities is a cell by data alone; the
reference checks categorical splits without arrays of a column's arity;
an altered left set reads `correct: false`."""
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

import cell_small

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(DATA))

import mixed  # noqa: E402
import reference  # noqa: E402

CELL = "mixed.exact"
FIELDS = ("feature", "threshold", "children", "n_node", "value", "depth")


def mixed_cell(root: Path) -> dict:
    """A copy of the benchmark under `root` with the mixed configuration,
    its traffic and a cell added as files and entries only."""
    import harness
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for name in ("mixed.json", "mixed.py"):
        shutil.copy(DATA / name, root / "bench" / "configs" / name)
    shutil.copy(DATA / "exact.mixed.json",
                root / "bench" / "traffic" / "exact.mixed.json")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": CELL, "config": "mixed",
                              "traffic": "exact.mixed", "chips": 1,
                              "why": "a test cell"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "higgs.exact" in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return harness.load_cell(CELL, root=root)


def run_mixed(root: Path, seed=2 ** 31 + 21):
    import jax

    import run
    return run.run_cell(CELL, seed, 0.2, 0, devices=jax.devices()[:1],
                        cell=mixed_cell(root))


def test_categorical_configuration_is_a_cell_by_data(tmp_path):
    line = run_mixed(tmp_path)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"tree_rows_per_s", "setup_s"}
    assert line["window_compiles"] == 0


def test_altered_left_set_is_not_correct(tmp_path, monkeypatch):
    """One category of one categorical node of tree 0 moves to the right
    where the tree is built."""
    from repro.core import forest as forest_lib
    build = forest_lib.tree_lib.build_forest
    moved = []

    def altered(*a, **kw):
        trees, stats = build(*a, **kw)
        for idx, t in zip(kw["tree_indices"], trees):
            if idx == 0:
                node = np.flatnonzero(t.is_cat)[0]
                moved.append(np.flatnonzero(t.cat_mask[node])[0])
                t.cat_mask[node, moved[-1]] = False
        return trees, stats
    monkeypatch.setattr(forest_lib.tree_lib, "build_forest", altered)
    line = run_mixed(tmp_path)
    assert moved
    assert not line["correct"], line["checks"]


def test_reference_builds_the_programs_trees_at_arity_2_20():
    """The widest column's ids are spread over [0, 2^20): the program
    builds (leaf, stat, 2^20) tables, the reference only groups the ids
    a node meets."""
    from repro.core.dataset import from_numpy
    from repro.core.forest import RandomForest
    from repro.core.tree import TreeParams
    num, cat, y = mixed.generate(2000, 1234)
    cat[:, 2] = cat[:, 2] * 255 + 17
    arities = (3, 40, 1 << 20)
    seed, depth = 2 ** 31 - 7, 4
    rf = RandomForest(params=TreeParams(max_depth=depth), num_trees=1,
                      seed=seed).fit(from_numpy(num, cat, y, arities=arities))
    data = reference.Data(num, y, 2, "exact", cat=cat, arities=arities)
    tree = rf.trees[0]
    ref = reference.build_tree(data, seed, 0, max_depth=depth)
    for f in FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(tree, f)), ref[f],
                                      err_msg=f)
    cat_nodes = np.flatnonzero(tree.feature >= data.m_num)
    assert any(tree.feature[cat_nodes] == data.m_num + 2)
    assert sorted(ref["cat_left"]) == cat_nodes.tolist()
    for nd in cat_nodes:
        np.testing.assert_array_equal(reference.cat_left(tree, nd),
                                      ref["cat_left"][int(nd)])
    res = reference.check_tree(tree, data, seed, 0, max_depth=depth)
    assert res["node_errors"] == 0 and res["gain_gap"] < 1e-4, res


def test_cat_left_reads_the_dense_mask_or_the_trees_accessor():
    """`reference.cat_left` gives the sorted int32 ids of the dense mask,
    and prefers a tree's own `cat_left` where it has one."""
    from repro.core.dataset import from_numpy
    from repro.core.forest import RandomForest
    from repro.core.tree import TreeParams
    num, cat, y = mixed.generate(1500, 99)
    rf = RandomForest(params=TreeParams(max_depth=4), num_trees=1,
                      seed=3).fit(from_numpy(num, cat, y,
                                             arities=mixed.ARITIES))
    tree = rf.trees[0]
    nodes = np.flatnonzero(tree.is_cat)
    assert nodes.size
    for nd in nodes:
        ids = reference.cat_left(tree, nd)
        assert ids.dtype == np.int32 and np.all(np.diff(ids) > 0)
        np.testing.assert_array_equal(ids, np.flatnonzero(tree.cat_mask[nd]))

    class Sparse:
        def cat_left(self, node):
            return [node, node + 1]
    np.testing.assert_array_equal(reference.cat_left(Sparse(), 5), [5, 6])


@pytest.mark.parametrize("ids,err", [([-1, 2], 1), ([2, 40], 1), ([2], 0)])
def test_left_ids_outside_the_arity_are_node_errors(ids, err):
    """A root split on the 40-ary column whose left set holds `ids`."""
    num, cat, y = mixed.generate(600, 5)
    data = reference.Data(num, y, 2, "exact", cat=cat, arities=mixed.ARITIES)
    w = reference.bag_weights(11, 0, data.n)
    stump = {"feature": np.array([data.m_num + 1, -1, -1]),
             "threshold": np.zeros(3, np.float32),
             "children": np.array([[1, 2], [-1, -1], [-1, -1]]),
             "value": np.zeros((3, 2)), "n_node": np.zeros(3),
             "depth": np.array([0, 1, 1]), "cat_left": {0: np.array(ids)}}
    res = reference.check_tree(stump, data, 11, 0, max_depth=1)
    assert res["errors"].get("cat_id_out_of_range", {"count": 0}
                             )["count"] == err
    assert res["node_errors"] > 0 and w.sum() > 0    # weights are off too
