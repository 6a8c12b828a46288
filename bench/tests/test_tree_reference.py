"""The plain references agree with the program node for node at a small
size on the CPU, and a perturbed gain is caught."""
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "configs")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import covertype  # noqa: E402
import higgs  # noqa: E402
import reference  # noqa: E402
import serve  # noqa: E402

FIELDS = ("feature", "threshold", "children", "n_node", "value", "depth")
GAP_LIMIT = json.loads((BENCH / "traffic" / "hist.json").read_text()
                       )["limits"]["gain_gap"]


def _forest(gen, n, mode, depth, seed, trees=2):
    from repro.core.dataset import from_numpy
    from repro.core.forest import RandomForest
    from repro.core.tree import TreeParams
    X, y = gen.generate(n, 1234)
    rf = RandomForest(params=TreeParams(max_depth=depth, split_mode=mode),
                      num_trees=trees, seed=seed).fit(from_numpy(X, None, y))
    return X, y, rf


CASES = [(higgs, 3000, "hist", 6), (higgs, 3000, "exact", 6),
         (covertype, 4000, "hist", 7), (covertype, 4000, "exact", 7)]


@pytest.mark.parametrize("gen,n,mode,depth", CASES,
                         ids=["higgs-hist", "higgs-exact", "cover-hist",
                              "cover-exact"])
def test_reference_builds_the_programs_trees(gen, n, mode, depth):
    seed = 2 ** 31 - 99
    X, y, rf = _forest(gen, n, mode, depth, seed)
    data = reference.Data(X, y, int(y.max()) + 1, mode)
    for t, tree in enumerate(rf.trees):
        ref = reference.build_tree(data, seed, t, max_depth=depth)
        for f in FIELDS:
            np.testing.assert_array_equal(np.asarray(getattr(tree, f)),
                                          ref[f], err_msg=f"tree {t}: {f}")
        res = reference.check_tree(tree, data, seed, t, max_depth=depth)
        assert res["node_errors"] == 0 and res["gain_gap"] < GAP_LIMIT
        assert res["nodes"] == tree.num_nodes > 7


def test_perturbed_gain_is_caught(monkeypatch):
    """A builder whose gain favours later columns grows other trees, and
    the check reads them as wrong."""
    seed, depth = 77, 6
    X, y, rf = _forest(higgs, 3000, "hist", depth, seed, trees=1)
    data = reference.Data(X, y, 2, "hist")
    true_gain = reference._gain

    def tilted(left, right, dtype):
        noise = np.random.default_rng(left.size).random(left.shape[:-1])
        return true_gain(left, right, dtype) * (1 + 0.05 * noise)
    monkeypatch.setattr(reference, "_gain", tilted)
    bad = reference.build_tree(data, seed, 0, max_depth=depth)
    monkeypatch.setattr(reference, "_gain", true_gain)
    same = all(np.array_equal(np.asarray(getattr(rf.trees[0], f)), bad[f])
               for f in FIELDS)
    assert not same
    res = reference.check_tree(bad, data, seed, 0, max_depth=depth)
    assert res["gain_gap"] > GAP_LIMIT


def test_forest_descent_matches_the_server(tmp_path):
    import jax.numpy as jnp

    from repro.core.forest import PackedForest
    from repro.serve.engine import ForestServer
    pool, _ = higgs.generate(4096, 5)
    f = serve.make_forest(5, pool, num_trees=7, depth=6)
    T, N = f["feature"].shape
    PackedForest(feature=jnp.asarray(f["feature"]),
                 threshold=jnp.asarray(f["threshold"]),
                 is_cat=jnp.zeros((T, N), bool),
                 cat_mask=jnp.zeros((T, N, 1), bool),
                 children=jnp.asarray(f["children"]),
                 value=jnp.asarray(f["value"]), m_num=28,
                 iters=7).save(tmp_path / "f.npz")
    srv = ForestServer.load(tmp_path / "f.npz", warm_batch_sizes=(64,))
    got = np.asarray(srv.predict(pool[:64]))
    np.testing.assert_allclose(got, reference.forest_proba(f, pool[:64]),
                               rtol=0, atol=1e-6)
