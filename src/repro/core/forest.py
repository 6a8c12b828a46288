"""Random Forest manager (paper §2.5) + stacked forest inference.

"To train a Random Forest, the manager queries in parallel the tree
builders.  This query contains the index of the requested tree (the tree
index is used in the seeding, §2.2) as well as a list of splitters ..."

The manager here is the host loop: each tree is trained by `tree.build_tree`
(the tree-builder) against the shared presorted dataset (the splitters'
columns).  Trees are independent — on a real cluster DRF trains them in
parallel on replicated splitters; we expose `predict`, OOB scoring and
distributed feature importance on top.

Inference is batched over the whole forest: `fit` packs every tree into one
set of padded flat arrays (`PackedForest`) and `predict_proba` is a single
jitted vmap-over-trees descent — one device program for a 100-tree forest
instead of a per-tree Python loop with a retrace per tree (the per-tree
`iters` used to be a distinct static argument for every tree).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import bagging, presort, tree as tree_lib
from repro.core.dataset import TabularDataset
from repro.core.level.engines import SplitEngine


# ---------------------------------------------------------------------------
# Stacked forest inference
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PackedForest:
    """All trees of a forest in one set of padded flat arrays.

    `pack_trees` pads every tree to the forest maxima — N = max node count,
    V = max categorical arity, C = max value width — and stacks them, so a
    T-tree forest is six device arrays with a leading tree axis (shapes
    below) instead of T Python objects.  This is what makes whole-forest
    inference ONE jitted program (`RandomForest.predict_proba`): a vmap
    over the tree axis of a fori_loop descent with the single static
    iteration bound `iters`.

    Nodes beyond a tree's `num_nodes` are padding leaves (feature −1,
    value 0); they are unreachable because the descent starts at node 0 and
    leaves are absorbing.  Feature ids < `m_num` are numeric (threshold
    rule x <= thr), the rest categorical (membership in `cat_mask`).
    """
    feature: jnp.ndarray     # (T, N) int32; -1 = leaf
    threshold: jnp.ndarray   # (T, N) float32
    is_cat: jnp.ndarray      # (T, N) bool
    cat_mask: jnp.ndarray    # (T, N, V) bool
    children: jnp.ndarray    # (T, N, 2) int32
    value: jnp.ndarray       # (T, N, C) float32
    m_num: int
    iters: int               # max depth over trees + 1 (static descent bound)

    FORMAT_VERSION = 1       # bump on any array-layout change

    @property
    def num_trees(self) -> int:
        return int(self.feature.shape[0])

    # -- stable export path (ROADMAP "Serving") ------------------------
    _ARRAYS = ("feature", "threshold", "is_cat", "cat_mask", "children",
               "value")

    def save(self, path) -> None:
        """Serialize to ONE .npz file with a format-version field.

        The file is self-contained: `PackedForest.load` + `predict_proba`
        is a full batched-inference stack with no Tree objects, no
        training code path, and no pickle (plain npz arrays only) — the
        stable boundary a serving process loads across repo versions.

        Written atomically (tmp + `os.replace`, DESIGN.md §9): a crash
        mid-save leaves either the previous complete model or the new
        one, never a truncated .npz a server would fail to load.
        """
        import os

        from repro.core import atomicio
        p = os.fspath(path)
        if not p.endswith(".npz"):
            p += ".npz"          # numpy's suffix rule, applied up front
        arrays = dict(
            format_version=np.int32(self.FORMAT_VERSION),
            m_num=np.int32(self.m_num), iters=np.int32(self.iters),
            **{k: np.asarray(getattr(self, k)) for k in self._ARRAYS})
        atomicio.atomic_replace(
            p, lambda tmp: np.savez_compressed(open(tmp, "wb"), **arrays))

    @classmethod
    def load(cls, path) -> "PackedForest":
        """Load an .npz written by `save` (version-checked).

        Accepts the same path string `save` was given: numpy appends
        ".npz" to suffix-less filenames at save time, so retry with it.
        """
        import os
        p = os.fspath(path)
        if not os.path.exists(p) and not p.endswith(".npz"):
            p += ".npz"
        with np.load(p) as z:
            version = int(z["format_version"])
            if version != cls.FORMAT_VERSION:
                raise ValueError(
                    f"PackedForest format v{version} not supported "
                    f"(this build reads v{cls.FORMAT_VERSION})")
            return cls(m_num=int(z["m_num"]), iters=int(z["iters"]),
                       **{k: jnp.asarray(z[k]) for k in cls._ARRAYS})

    def predict_proba(self, num, cat, reduce_mean: bool = True):
        """Batched inference straight off the packed arrays: ONE jitted
        call for the whole forest — (B, C) forest mean, or (T, B, C) with
        `reduce_mean=False` (see `examples/forest_export.py`)."""
        return _forest_predict(
            self.feature, self.threshold, self.is_cat, self.cat_mask,
            self.children, self.value, jnp.asarray(num, jnp.float32),
            jnp.asarray(cat, jnp.int32), self.m_num, self.iters,
            reduce_mean)


def pack_trees(trees: list) -> PackedForest:
    """Pad each tree's flat arrays to the forest maximum and stack."""
    assert trees
    T = len(trees)
    N = max(t.num_nodes for t in trees)
    V = max(t.cat_mask.shape[1] for t in trees)
    C = max(t.value.shape[1] for t in trees)
    feature = np.full((T, N), -1, np.int32)
    threshold = np.zeros((T, N), np.float32)
    is_cat = np.zeros((T, N), bool)
    cat_mask = np.zeros((T, N, V), bool)
    children = np.full((T, N, 2), -1, np.int32)
    value = np.zeros((T, N, C), np.float32)
    for t, tr in enumerate(trees):
        k = tr.num_nodes
        feature[t, :k] = tr.feature
        threshold[t, :k] = tr.threshold
        is_cat[t, :k] = tr.is_cat
        cat_mask[t, :k, :tr.cat_mask.shape[1]] = tr.cat_mask
        children[t, :k] = tr.children
        value[t, :k, :tr.value.shape[1]] = tr.value
    iters = max(int(t.depth.max()) for t in trees) + 1
    return PackedForest(
        feature=jnp.asarray(feature), threshold=jnp.asarray(threshold),
        is_cat=jnp.asarray(is_cat), cat_mask=jnp.asarray(cat_mask),
        children=jnp.asarray(children), value=jnp.asarray(value),
        m_num=trees[0].m_num, iters=iters)


def _forest_predict_impl(feature, threshold, is_cat, cat_mask, children,
                         value, num, cat, m_num, iters, reduce_mean):
    # runs only at trace time: tests assert predict_proba compiles ONCE
    # for a whole forest (no per-tree retraces)
    obs.count("predict.traces")
    B = num.shape[0] if num.size else cat.shape[0]

    def one_tree(f, th, ic, cm, ch, val):
        node = jnp.zeros((B,), jnp.int32)

        def body(_, node):
            ff = f[node]
            leaf = ff < 0
            jn = jnp.clip(ff, 0, max(m_num - 1, 0))
            jc = jnp.clip(ff - m_num, 0, max(cat.shape[1] - 1, 0))
            xnum = (jnp.take_along_axis(num, jn[:, None], 1)[:, 0]
                    if num.size else jnp.zeros((B,), jnp.float32))
            xcat = (jnp.take_along_axis(cat, jc[:, None], 1)[:, 0]
                    if cat.size else jnp.zeros((B,), jnp.int32))
            go_left = jnp.where(ic[node], cm[node, xcat], xnum <= th[node])
            nxt = jnp.where(go_left, ch[node, 0], ch[node, 1])
            return jnp.where(leaf, node, nxt)

        node = jax.lax.fori_loop(0, iters, body, node)
        return val[node]                                      # (B, C)

    preds = jax.vmap(one_tree)(feature, threshold, is_cat, cat_mask,
                               children, value)               # (T, B, C)
    if not reduce_mean:
        return preds
    # trees summed one by one, then one division: elementwise steps in a
    # fixed order, so a row's answer does not depend on the batch it
    # comes in.  A TPU associates a `mean` reduction differently per batch
    # shape (last-bit differences between ForestServer's batches and a
    # whole-set predict_proba on a v5e).
    total = jax.lax.fori_loop(1, preds.shape[0],
                              lambda t, acc: acc + preds[t], preds[0])
    return total / preds.shape[0]


_forest_predict = jax.jit(
    _forest_predict_impl,
    static_argnames=("m_num", "iters", "reduce_mean"))


@dataclasses.dataclass
class RandomForest:
    """The paper's DRF: an exact Random Forest trained level by level.

    Construction params:
      params:     `tree.TreeParams` — depth/impurity/backend etc.; see its
                  fields for the paper hyper-parameters (m', min_records,
                  USB, Sprint pruning).  `split_mode="hist"` trains the
                  PLANET-style approximate baseline (<= num_bins threshold
                  buckets per numeric column, DESIGN.md §6) on the same
                  fused level machinery; `"exact"` (default) is the
                  paper's exact search.
      num_trees:  forest size T.
      seed:       forest seed; ALL randomness (bagging, candidate features)
                  is a pure function of (seed, tree index) — the paper's
                  zero-communication seeding (§2.2).
      tree_batch: how many trees to train per batched device program
                  (DESIGN.md §3).  None (default) picks a memory-bounded
                  batch automatically; 1 forces the per-tree builder; any
                  k > 1 trains the forest in ⌈T/k⌉ chunks, each chunk
                  issuing ONE jitted program per depth level for all its
                  trees.  Trees are bit-identical for every choice.

    `fit(ds)` trains on a `TabularDataset` and packs the trees into a
    `PackedForest`, after which `predict` / `predict_proba` (forest mean,
    (B, C)) and `predict_proba_per_tree` ((T, B, C)) are each ONE jitted
    device call regardless of T.  `oob_score`, `auc`, and
    `feature_importances` are the paper's evaluation utilities.
    """

    params: tree_lib.TreeParams
    num_trees: int = 10
    seed: int = 0
    tree_batch: Optional[int] = None

    trees: list = dataclasses.field(default_factory=list)
    level_stats: list = dataclasses.field(default_factory=list)
    num_classes: int = 2
    m: int = 0
    m_num: int = 0
    packed: Optional[PackedForest] = None

    # ------------------------------------------------------------------
    def _resolve_tree_batch(self, ds: TabularDataset) -> int:
        """Trees per batched level program (1 = per-tree builder).

        The auto heuristic bounds the batched step's largest row-indexed
        intermediate (T·m_num·n elements, ~256 MB f32) and caps at 16 —
        past that the programs are compute-bound and batching wider only
        adds memory pressure.
        """
        if self.tree_batch is not None:
            return max(1, min(int(self.tree_batch), self.num_trees))
        per_tree = max(1, max(ds.m_num, 1) * ds.n)
        return int(max(1, min(self.num_trees, 16, (1 << 26) // per_tree)))

    def fit(self, ds: TabularDataset, collect_stats: bool = False,
            supersplit_fn=None, engine=None,
            cat_engine=None) -> "RandomForest":
        """Train the forest; one batched device program per depth level.

        Trees are chunked into `tree_batch`-sized groups and each group is
        built by `tree.build_forest` — the fused level step vmapped over
        the tree axis.  EVERY mode runs through that one plan: local or
        mesh-sharded engines (`engine=` / `cat_engine=`, see
        `repro.core.level`), exact or hist, with or without Sprint pruning
        (`prune_closed_frac`).  The only fallback to the per-tree
        `tree.build_tree` loop is a LEGACY bare `supersplit_fn` closure
        (the pre-engine API), which composes with neither the tree-axis
        vmap nor the batch-native protocol — passing one emits a
        UserWarning and forces `tree_batch=1`; pass a `SplitEngine`
        instead to keep tree batching.  Trees are identical either way,
        only the dispatch count changes.
        """
        from repro.core.dataset import RowSource
        if isinstance(ds, RowSource):
            raise TypeError(
                "fit() trains from a fully materialized TabularDataset; "
                "for a RowSource (out-of-core bin cache) use "
                "fit_streamed(source)")
        with obs.span("repro.fit", trees=self.num_trees):
            return self._fit(ds, collect_stats, supersplit_fn, engine,
                             cat_engine)

    def _fit(self, ds, collect_stats, supersplit_fn, engine, cat_engine):
        ds.validate()
        self.num_classes = ds.num_classes
        self.m, self.m_num = ds.m, ds.m_num
        # §2.1 dataset preparation: presort once, reuse for every tree.
        if ds.m_num:
            with obs.span("repro.fit.presort"):
                sorted_idx = presort.presort_columns(ds.num)
                sorted_vals = presort.gather_sorted(ds.num, sorted_idx)
        else:
            sorted_idx = jnp.zeros((0, ds.n), jnp.int32)
            sorted_vals = jnp.zeros((0, ds.n), jnp.float32)
        kw = dict(num=ds.num, cat=ds.cat, labels=ds.labels,
                  sorted_vals=sorted_vals, sorted_idx=sorted_idx,
                  arities=ds.arities, num_classes=ds.num_classes,
                  params=self.params, seed=self.seed,
                  collect_stats=collect_stats,
                  engine=engine, cat_engine=cat_engine)
        if self.params.split_mode == "hist" and ds.m_num:
            # hist mode: quantize once per forest (the PLANET-style fixed
            # bucket budget), shared by every tree/level like the presort
            with obs.span("repro.fit.quantize"):
                bin_of, bin_edges = presort.quantize(ds.num, sorted_vals,
                                                     self.params.num_bins)
            kw.update(bin_of=bin_of, bin_edges=bin_edges)
        if supersplit_fn is not None and engine is not None:
            raise ValueError(
                "pass either engine= (a SplitEngine) or supersplit_fn=, "
                "not both — one of them would be silently ignored")
        if isinstance(supersplit_fn, SplitEngine):
            # the engine API replaces supersplit_fn; accept it here too
            kw["engine"] = supersplit_fn
            supersplit_fn = None
        tb = self._resolve_tree_batch(ds)
        if supersplit_fn is not None:
            warnings.warn(
                "legacy supersplit_fn closures force the per-tree builder "
                "(tree_batch=1, one level program per depth PER TREE); "
                "pass a repro.core.level SplitEngine (engine=...) to keep "
                "the batched one-program-per-depth path",
                UserWarning, stacklevel=3)   # the caller of fit()
            tb = 1                      # per-tree-only configuration
        self.trees, self.level_stats = [], []
        if tb > 1:
            for lo in range(0, self.num_trees, tb):
                hi = min(lo + tb, self.num_trees)
                with obs.span("repro.forest.batch", batch=f"{lo}:{hi}"):
                    trees, stats = tree_lib.build_forest(
                        tree_indices=range(lo, hi), **kw)
                self.trees.extend(trees)
                self.level_stats.extend(stats)
        else:
            for t in range(self.num_trees):
                with obs.span("repro.forest.batch", batch=f"{t}:{t + 1}"):
                    tr, stats = tree_lib.build_tree(
                        tree_idx=t, supersplit_fn=supersplit_fn, **kw)
                self.trees.append(tr)
                self.level_stats.append(stats)
        with obs.span("repro.forest.pack"):
            self.packed = pack_trees(self.trees)  # stacked inference arrays
        return self

    def fit_streamed(self, source, collect_stats: bool = False,
                     engine=None, checkpoint_dir: Optional[str] = None,
                     checkpoint_every: int = 1,
                     resume: bool = False) -> "RandomForest":
        """Train the forest out-of-core from a `dataset.RowSource`.

        Same trees as `fit` on the equivalently quantized in-memory
        dataset (bit-identical node for node, tests/test_stream_parity.py)
        but the per-row state stays host-resident — the level programs see
        only fixed-shape chunks of the bit-packed bin cache, so peak
        device memory is bounded by `source.chunk_size`, not n.  Hist
        split mode + classification + numeric columns only (the
        `tree.build_forest_streamed` restrictions).

        Fault tolerance (DESIGN.md §9): `checkpoint_dir=` snapshots the
        in-flight tree batch's host state every `checkpoint_every`
        levels and commits each finished batch, all atomically;
        `resume=True` skips committed batches, restores the in-flight
        one at its last snapshotted level, and finishes the forest
        bit-identically to an uninterrupted fit.  Resuming against a
        different source / params / seed raises
        `checkpoint.CheckpointMismatchError`.  Under multi-host
        sharding only process 0 writes; every host fingerprint-checks.
        """
        from repro.core.dataset import RowSource, TabularDataset
        if isinstance(source, TabularDataset):
            raise TypeError(
                "fit_streamed() trains from a RowSource; wrap the dataset "
                "with ArrayRowSource.from_dataset(ds, num_bins) (or use "
                "plain fit(ds))")
        if not isinstance(source, RowSource):
            raise TypeError(f"expected a dataset.RowSource, got "
                            f"{type(source).__name__}")
        self.num_classes = source.num_classes
        self.m = self.m_num = source.m_num
        ck = None
        if checkpoint_dir is not None:
            from repro.core import checkpoint as checkpoint_lib
            ck = checkpoint_lib.StreamCheckpointer(checkpoint_dir,
                                                   every=checkpoint_every)
            ck.prepare(source=source, params=self.params, seed=self.seed,
                       resume=resume)
        tb = (max(1, min(int(self.tree_batch), self.num_trees))
              if self.tree_batch is not None else min(self.num_trees, 16))
        self.trees, self.level_stats = [], []
        for lo in range(0, self.num_trees, tb):
            hi = min(lo + tb, self.num_trees)
            with obs.span("repro.forest.batch", batch=f"{lo}:{hi}"):
                trees, stats = tree_lib.build_forest_streamed(
                    source=source, tree_indices=range(lo, hi),
                    params=self.params, seed=self.seed,
                    collect_stats=collect_stats, engine=engine,
                    resume=resume, _checkpointer=ck)
            self.trees.extend(trees)
            self.level_stats.extend(stats)
        with obs.span("repro.forest.pack"):
            self.packed = pack_trees(self.trees)
        return self

    # ------------------------------------------------------------------
    def _packed_forest(self, up_to: Optional[int] = None) -> PackedForest:
        assert self.trees, "fit first"
        if self.packed is None or self.packed.num_trees != len(self.trees):
            self.packed = pack_trees(self.trees)
        pk = self.packed
        if up_to is not None and up_to < pk.num_trees:
            pk = dataclasses.replace(
                pk, feature=pk.feature[:up_to], threshold=pk.threshold[:up_to],
                is_cat=pk.is_cat[:up_to], cat_mask=pk.cat_mask[:up_to],
                children=pk.children[:up_to], value=pk.value[:up_to])
        return pk

    def predict_proba(self, num, cat, up_to: Optional[int] = None) -> jnp.ndarray:
        """Forest-averaged distributions in ONE jitted call (vmap over the
        packed trees — no per-tree Python loop, no per-tree retrace)."""
        pk = self._packed_forest(up_to)
        return _forest_predict(
            pk.feature, pk.threshold, pk.is_cat, pk.cat_mask, pk.children,
            pk.value, jnp.asarray(num, jnp.float32), jnp.asarray(cat, jnp.int32),
            pk.m_num, pk.iters, True)

    def predict_proba_per_tree(self, num, cat) -> jnp.ndarray:
        """(T, B, C) per-tree predictions, one jitted call (OOB, analysis)."""
        pk = self._packed_forest()
        return _forest_predict(
            pk.feature, pk.threshold, pk.is_cat, pk.cat_mask, pk.children,
            pk.value, jnp.asarray(num, jnp.float32), jnp.asarray(cat, jnp.int32),
            pk.m_num, pk.iters, False)

    def predict(self, num, cat) -> jnp.ndarray:
        p = self.predict_proba(num, cat)
        if self.params.task == "classification":
            return jnp.argmax(p, axis=-1)
        return p[:, 0]

    # ------------------------------------------------------------------
    def oob_score(self, ds: TabularDataset) -> float:
        """Out-of-bag accuracy using the seeded bagging (zero extra state)."""
        n = ds.n
        correct = np.zeros(n)
        counted = np.zeros(n)
        oob_masks = [
            np.asarray(bagging.bag_counts(self.seed, t, n,
                                          self.params.bagging)) == 0
            for t in range(len(self.trees))]
        if not any(m.any() for m in oob_masks):   # e.g. bagging == "none"
            return float("nan")
        # one device program for all trees; argmax on device so only the
        # (T, B) class ids cross to the host
        preds = np.asarray(jnp.argmax(
            self.predict_proba_per_tree(ds.num, ds.cat), axis=-1))
        labels = np.asarray(ds.labels)
        for t, oob in enumerate(oob_masks):
            if not oob.any():
                continue
            correct[oob] += preds[t][oob] == labels[oob]
            counted[oob] += 1
        mask = counted > 0
        return float((correct[mask] / counted[mask]).mean()) if mask.any() else float("nan")

    # ------------------------------------------------------------------
    def feature_importances(self) -> np.ndarray:
        """Mean decrease in impurity, computed per-splitter then merged —
        the paper's "distributed computing of feature importance"."""
        from repro.core import importance
        return importance.mdi_importance(self.trees, self.m)

    def auc(self, ds: TabularDataset) -> float:
        """Binary AUC (the paper's headline metric on Leo / Fig. 1)."""
        assert self.num_classes == 2
        scores = np.asarray(self.predict_proba(ds.num, ds.cat))[:, 1]
        y = np.asarray(ds.labels)
        order = np.argsort(scores, kind="stable")
        ranks = np.empty_like(order, dtype=np.float64)
        ranks[order] = np.arange(1, len(y) + 1)
        # average ranks over ties
        s_sorted = scores[order]
        uniq, inv, cnts = np.unique(s_sorted, return_inverse=True, return_counts=True)
        start = np.concatenate([[0], np.cumsum(cnts)[:-1]])
        avg = start + (cnts + 1) / 2.0
        ranks[order] = avg[inv]
        n1 = (y == 1).sum()
        n0 = (y == 0).sum()
        if n1 == 0 or n0 == 0:
            return float("nan")
        u = ranks[y == 1].sum() - n1 * (n1 + 1) / 2.0
        return float(u / (n1 * n0))
