"""A training run with the timed path broken underneath reads
`correct: false`; unbroken, it reads true.  (One chip: no exchange
between chips to leave out.)"""
import dataclasses

import numpy as np
import pytest

import cell_small

CELLS = ["higgs.hist", "higgs.exact", "covertype.hist.deep"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    line = cell_small.run(workload)
    assert line["correct"], line["checks"]
    assert line["window_compiles"] == 0


def _unchanged_state(monkeypatch):
    """The level step hands its state back unchanged: no leaf splits."""
    from repro.core import tree as tree_lib
    step = tree_lib._fused_level_step_batched

    def frozen(*args, **kw):
        struct, leaf_of, ord_idx, totals, tables = step(*args, **kw)
        struct = dict(struct, will_split=struct["will_split"] & False)
        return struct, args[8], ord_idx, totals, tables
    monkeypatch.setattr(tree_lib, "_fused_level_step_batched", frozen)


def _half_batch(monkeypatch):
    """The fit sees half of the rows."""
    from repro.core.forest import RandomForest
    fit = RandomForest.fit

    def half(self, ds, *a, **kw):
        k = ds.n // 2
        ds = dataclasses.replace(ds, num=ds.num[:k], cat=ds.cat[:k],
                                 labels=ds.labels[:k])
        return fit(self, ds, *a, **kw)
    monkeypatch.setattr(RandomForest, "fit", half)


def _altered_answer(monkeypatch):
    """One threshold of every tree is changed where the tree is built."""
    from repro.core import forest as forest_lib
    build = forest_lib.tree_lib.build_forest

    def altered(*a, **kw):
        trees, stats = build(*a, **kw)
        for t in trees:
            inner = np.flatnonzero(t.feature >= 0)
            t.threshold[inner[len(inner) // 2]] += np.float32(0.25)
        return trees, stats
    monkeypatch.setattr(forest_lib.tree_lib, "build_forest", altered)


def _altered_odd_trees(monkeypatch):
    """One threshold of each odd-indexed tree is changed where it is
    built: the second tree of each batch of two."""
    from repro.core import forest as forest_lib
    build = forest_lib.tree_lib.build_forest

    def altered(*a, **kw):
        trees, stats = build(*a, **kw)
        for idx, t in zip(kw["tree_indices"], trees):
            if idx % 2:
                inner = np.flatnonzero(t.feature >= 0)
                t.threshold[inner[len(inner) // 2]] += np.float32(0.25)
        return trees, stats
    monkeypatch.setattr(forest_lib.tree_lib, "build_forest", altered)


FAULTS = {"unchanged_state": _unchanged_state, "half_batch": _half_batch,
          "altered_answer": _altered_answer}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS[:2])
def test_broken_run_is_not_correct(monkeypatch, workload, fault):
    FAULTS[fault](monkeypatch)
    line = cell_small.run(workload)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("T,k", [(2, 2), (4, 2), (4, 4), (8, 4), (3, 2)])
def test_check_picks_cover_every_batch_position(T, k):
    import train
    for seed in range(2 ** 31, 2 ** 31 + 64):
        picks = train.check_picks(seed, 3, T, k)
        assert len(set(picks)) == min(k, T)
        assert all(0 <= i < 3 and 0 <= t < T for i, t in picks)
        for b in range(1, k + 1):
            if T % b == 0:
                assert {t % b for _, t in picks} == set(range(b))


@pytest.mark.parametrize("seed", [2 ** 31 + 11, 2 ** 31 + 12, 2 ** 31 + 13])
def test_fault_in_one_batch_position_is_caught(monkeypatch, seed):
    _altered_odd_trees(monkeypatch)
    line = cell_small.run("higgs.hist", seed=seed, num_trees=4,
                          check_trees=2)
    assert not line["correct"], line["checks"]
