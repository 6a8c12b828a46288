"""Gradient Boosted Trees on the DRF substrate (paper §1, §2).

"While this paper mainly focuses on Random Forests, the proposed algorithm
can be applied to other DF models, notably Gradient Boosted Trees (Ye et
al., 2009).  In this case, while trees cannot be trained in parallel, the
training of each individual tree is still distributed."

Each boosting round fits a regression tree (variance impurity) to the
current pseudo-residuals with the SAME supersplit engine — the presort,
class list, seeded candidate draws and one-pass-per-level structure are all
shared (including `split_mode="hist"`, the PLANET-style approximate
baseline).  Losses: squared error (regression) and logistic (binary
classification).

Inference stacks the fitted rounds into a `forest.PackedForest`:
`predict_raw` is ONE jitted device call (vmap-over-rounds descent + the
scaled sum + base score fused), not a host-side tree loop.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import forest as forest_lib
from repro.core import presort, tree as tree_lib
from repro.core.dataset import TabularDataset


@dataclasses.dataclass
class GBTParams:
    num_rounds: int = 20
    learning_rate: float = 0.1
    max_depth: int = 4
    min_records: float = 1.0
    num_candidates: int | None = None   # None = all features (GBT default)
    loss: str = "squared"               # squared | logistic
    backend: str = "segment"
    split_mode: str = "exact"           # exact | hist (PLANET baseline)
    num_bins: int = 255                 # hist-mode bucket budget per column
    seed: int = 0


@functools.partial(jax.jit, static_argnames=("m_num", "iters"))
def _gbt_predict_raw_jit(feature, threshold, is_cat, cat_mask, children,
                         value, num, cat, base_score, learning_rate,
                         m_num, iters):
    """base + lr · Σ_rounds tree_t(x), one device program for all rounds.

    Reuses the stacked-forest descent (forest._forest_predict_impl, a vmap
    over the round axis of the packed arrays); the scaled reduction over
    rounds stays inside the same jit.
    """
    # runs only at trace time: tests assert predict_raw compiles ONCE for a
    # whole model (no per-round retraces)
    obs.count("gbt.raw_traces")
    preds = forest_lib._forest_predict_impl(
        feature, threshold, is_cat, cat_mask, children, value, num, cat,
        m_num, iters, reduce_mean=False)                     # (T, B, 1)
    return base_score + learning_rate * preds[:, :, 0].sum(axis=0)


@dataclasses.dataclass
class GBTModel:
    """Gradient Boosted Trees on the DRF tree builder (paper §1).

    Each boosting round fits one regression tree (variance impurity,
    `bagging="none"`, all features candidates by default) to the current
    pseudo-residuals with the same fused one-program-per-level builder as
    `RandomForest` — rounds are sequential (tree t+1 needs tree t's
    predictions), so GBT uses the per-tree builder, not the multi-tree
    batch.  `split_mode="hist"` quantizes numeric columns once before the
    first round and every round scores bucket boundaries only (the
    PLANET-style baseline; exact is the default).  Losses: `"squared"`
    (regression; `predict` returns the raw score) and `"logistic"` (binary
    classification; `predict` thresholds at 0, `predict_proba` returns
    (B, 2) probabilities).

    `fit(ds)` expects a `TabularDataset`; for `"logistic"` the labels must
    be 0/1 ints.  `base_score` is the fitted prior (mean / log-odds) that
    every prediction starts from.  Inputs to `predict*` are (B, m_num)
    numeric and (B, m_cat) categorical arrays, as for `RandomForest`.
    Fitted rounds are packed into a `forest.PackedForest` so `predict_raw`
    is ONE jitted device call regardless of the round count.
    """

    params: GBTParams
    trees: list = dataclasses.field(default_factory=list)
    base_score: float = 0.0
    m: int = 0
    packed: Optional[forest_lib.PackedForest] = None

    def fit(self, ds: TabularDataset, engine=None,
            cat_engine=None) -> "GBTModel":
        """Fit the boosted rounds; `engine`/`cat_engine` optionally select
        `repro.core.level` SplitEngines (e.g. the mesh-sharded ones) — each
        round's tree runs through the same LevelPlan as RandomForest."""
        p = self.params
        self.m = ds.m
        y = np.asarray(ds.labels, np.float64)
        if p.loss == "logistic":
            pbar = np.clip(y.mean(), 1e-6, 1 - 1e-6)
            self.base_score = float(np.log(pbar / (1 - pbar)))
        else:
            self.base_score = float(y.mean())
        f = np.full_like(y, self.base_score, dtype=np.float64)

        if ds.m_num:
            sorted_idx = presort.presort_columns(ds.num)
            sorted_vals = presort.gather_sorted(ds.num, sorted_idx)
        else:
            sorted_idx = jnp.zeros((0, ds.n), jnp.int32)
            sorted_vals = jnp.zeros((0, ds.n), jnp.float32)

        tparams = tree_lib.TreeParams(
            max_depth=p.max_depth, min_records=p.min_records,
            num_candidates=p.num_candidates or ds.m, impurity="variance",
            task="regression", backend=p.backend, bagging="none",
            split_mode=p.split_mode, num_bins=p.num_bins)
        # hist mode: quantize once, before the first round — the bucket
        # state depends only on the columns, not on the residuals
        bin_of = bin_edges = None
        if p.split_mode == "hist" and ds.m_num:
            bin_of, bin_edges = presort.quantize(ds.num, sorted_vals,
                                                 p.num_bins)

        for t in range(p.num_rounds):
            if p.loss == "logistic":
                prob = 1.0 / (1.0 + np.exp(-f))
                resid = y - prob                       # negative gradient
            else:
                resid = y - f
            tr, _ = tree_lib.build_tree(
                num=ds.num, cat=ds.cat,
                labels=jnp.asarray(resid, jnp.float32),
                sorted_vals=sorted_vals, sorted_idx=sorted_idx,
                arities=ds.arities, num_classes=2,
                params=tparams, seed=p.seed, tree_idx=t,
                bin_of=bin_of, bin_edges=bin_edges,
                engine=engine, cat_engine=cat_engine)
            self.trees.append(tr)
            step = np.asarray(tr.predict_raw(ds.num, ds.cat))[:, 0]
            f = f + p.learning_rate * step
        if self.trees:                        # num_rounds=0: prior only
            self.packed = forest_lib.pack_trees(self.trees)
        return self

    def _packed(self) -> forest_lib.PackedForest:
        assert self.trees, "fit first"
        if self.packed is None or self.packed.num_trees != len(self.trees):
            self.packed = forest_lib.pack_trees(self.trees)
        return self.packed

    def predict_raw(self, num, cat) -> np.ndarray:
        """Raw boosted score, (B,) — ONE jitted call for all rounds."""
        if not self.trees:                    # num_rounds=0: the prior
            B = (np.asarray(num).shape[0] if np.asarray(num).size
                 else np.asarray(cat).shape[0])
            return np.full((B,), self.base_score, np.float32)
        pk = self._packed()
        return np.asarray(_gbt_predict_raw_jit(
            pk.feature, pk.threshold, pk.is_cat, pk.cat_mask, pk.children,
            pk.value, jnp.asarray(num, jnp.float32),
            jnp.asarray(cat, jnp.int32), jnp.float32(self.base_score),
            jnp.float32(self.params.learning_rate), pk.m_num, pk.iters))

    def predict(self, num, cat) -> np.ndarray:
        f = self.predict_raw(num, cat)
        if self.params.loss == "logistic":
            return (f > 0).astype(np.int32)
        return f

    def predict_proba(self, num, cat) -> np.ndarray:
        assert self.params.loss == "logistic"
        p1 = 1.0 / (1.0 + np.exp(-self.predict_raw(num, cat).astype(np.float64)))
        return np.stack([1 - p1, p1], -1)
