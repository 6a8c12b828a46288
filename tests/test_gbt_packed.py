"""GBT on the stacked predictor (ROADMAP item): `predict_raw` must be ONE
jitted device call over the packed rounds — no host-side tree loop, no
per-round retrace — and numerically match the explicit per-tree sum."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import tree as tree_lib
from repro.core.dataset import from_numpy
from repro.core.gbt import GBTModel, GBTParams


@pytest.fixture(scope="module")
def reg_ds():
    rng = np.random.default_rng(1)
    n = 800
    num = rng.normal(size=(n, 4)).astype(np.float32)
    y = (2 * num[:, 0] + num[:, 1] ** 2
         + 0.1 * rng.normal(size=n)).astype(np.float32)
    return from_numpy(num, None, y, task="regression")


def _host_loop_reference(gbt, num, cat):
    f = np.full((num.shape[0],), gbt.base_score)
    for tr in gbt.trees:
        f = f + gbt.params.learning_rate * np.asarray(
            tr.predict_raw(jnp.asarray(num, jnp.float32),
                           jnp.asarray(cat, jnp.int32)))[:, 0]
    return f


def test_predict_raw_single_call_no_tree_loop(reg_ds):
    gbt = GBTModel(GBTParams(num_rounds=10, max_depth=3,
                             learning_rate=0.3)).fit(reg_ds)
    assert gbt.packed is not None and gbt.packed.num_trees == 10
    ref = _host_loop_reference(gbt, np.asarray(reg_ds.num),
                               np.asarray(reg_ds.cat))

    # the per-tree descent path must be gone entirely
    def boom(*a, **k):
        raise AssertionError("per-tree _predict_jit used by predict_raw")
    saved = tree_lib._predict_jit
    tree_lib._predict_jit = boom
    try:
        traces0 = obs.counter("gbt.raw_traces")
        ptraces0 = obs.counter("predict.traces")
        f1 = gbt.predict_raw(reg_ds.num, reg_ds.cat)
        assert obs.counter("gbt.raw_traces") - traces0 <= 1       # one trace
        f2 = gbt.predict_raw(reg_ds.num, reg_ds.cat)
        assert obs.counter("gbt.raw_traces") - traces0 <= 1       # no retrace
        assert obs.counter("predict.traces") - ptraces0 <= 1
    finally:
        tree_lib._predict_jit = saved

    np.testing.assert_array_equal(f1, f2)
    np.testing.assert_allclose(f1, ref, atol=1e-4, rtol=1e-5)


def test_zero_rounds_returns_prior(reg_ds):
    """num_rounds=0 fits the prior only — no trees to pack, no crash."""
    g = GBTModel(GBTParams(num_rounds=0, max_depth=3)).fit(reg_ds)
    f = g.predict_raw(reg_ds.num, reg_ds.cat)
    np.testing.assert_allclose(
        f, np.full(reg_ds.n, g.base_score, np.float32), rtol=1e-6)


def test_logistic_predicts_through_packed_path():
    rng = np.random.default_rng(2)
    n = 700
    num = rng.normal(size=(n, 3)).astype(np.float32)
    yb = (num[:, 0] + num[:, 2] > 0).astype(np.int32)
    ds = from_numpy(num, None, yb)
    g = GBTModel(GBTParams(num_rounds=10, max_depth=3, learning_rate=0.3,
                           loss="logistic")).fit(ds)
    ref = _host_loop_reference(g, np.asarray(ds.num), np.asarray(ds.cat))
    np.testing.assert_allclose(g.predict_raw(ds.num, ds.cat), ref,
                               atol=1e-4, rtol=1e-5)
    proba = g.predict_proba(ds.num, ds.cat)
    assert proba.shape == (n, 2)
    np.testing.assert_allclose(proba.sum(-1), 1.0, atol=1e-6)
    acc = float((g.predict(ds.num, ds.cat) == yb).mean())
    assert acc > 0.9
