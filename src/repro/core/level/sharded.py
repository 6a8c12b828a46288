"""Mesh-sharded SplitEngines (paper §2 worker topology → shard_map).

Topology mapping (DESIGN.md §5):

  * `feature_axis` ("model") = the splitters: feature columns are sharded
    over it, each device searching optimal splits only on its own columns
    (paper: "each worker is assigned to a subset of columns ... read
    sequentially").
  * `row_axis` ("data") = row shards.  For the exact engine these are
    range-partitions of the PRESORTED order (beyond-paper 2-D extension):
    shard r of a column holds sorted rows [r·n/w, (r+1)·n/w), and exactness
    is preserved by resuming each shard's pass from the previous shard's
    histogram/value state — an all_gather of (ℓ+1)·S floats per leaf
    histogram, tiny compared to the data.  For the histogram and
    categorical engines rows shard in PLAIN row order and a single `psum`
    merges the fixed-size (ℓ+1)·V·S count tables — the paper's
    network-complexity contrast, executable side by side.

Every engine here is `batch_native`: the fused level step calls it ONCE
per depth with a leading tree axis T, and the shard_map body vmaps over
trees INSIDE the mesh program.  Sharded training therefore inherits the
multi-tree batch axis, the early-finish masking, and the device-resident
pruning of the batched builder with no special-cased host loop — D (not
T·D) device dispatches per forest, same as local training.

Engines also implement `__call__` with the original `supersplit_fn`
signatures, so existing call sites (launch/dryrun.py, older tests) keep
working unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import splits
from repro.core.level.engines import (SplitEngine, _class_ids,
                                      _expand_subtracted, _scatter_updates)

def _shmap(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@dataclasses.dataclass(frozen=True)
class _MeshEngine(SplitEngine):
    mesh: object = None         # jax.sharding.Mesh (hashable)
    feature_axis: str = "model"
    row_axis: Optional[str] = "data"

    batch_native = True

    def row_shards(self) -> int:
        if self.row_axis is None:
            return 1
        return int(self.mesh.shape[self.row_axis])


# ---------------------------------------------------------------------------
# Exact numeric engine: columns over "model", presorted rows over "data"
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedExactNumeric(_MeshEngine):
    """Exact supersplit with columns and (optionally) presorted rows sharded.

    Per column: each row shard computes (a) its local per-leaf stat totals
    and last in-bag value, (b) all_gathers them over `row_axis` (payload
    (L+1)·S floats — independent of n), (c) forms the exclusive shard
    prefix (h_init, v_init) and GLOBAL totals, and (d) runs the exact
    backend on its local slice resuming from that state.  Partial bests
    merge with a first-max over shards, matching the sequential scan
    order's tie-breaking.  `row_axis=None` is the paper's column-only
    splitter layout (rows replicated, no collectives).
    """
    backend: str = "segment"

    needs_sorted = True

    def supersplits(self, inp, st, Lp, cand):
        g, t = self._search(inp.sorted_vals, inp.sorted_idx,
                            inp.leaf_of[None], inp.w[None], inp.stats[None],
                            cand[None], Lp, st.impurity, st.task,
                            st.min_records)
        return g[0], t[0]

    def supersplits_batched(self, inp, st, Lp, cand):
        return self._search(inp.sorted_vals, inp.sorted_idx, inp.leaf_of,
                            inp.w, inp.stats, cand, Lp, st.impurity,
                            st.task, st.min_records)

    def __call__(self, sorted_vals, sorted_idx, leaf_of, w, stats, cand,
                 Lp, impurity, task, min_records):
        """Legacy per-tree supersplit_fn signature."""
        g, t = self._search(sorted_vals, sorted_idx, leaf_of[None], w[None],
                            stats[None], cand[None], Lp, impurity, task,
                            min_records)
        return g[0], t[0]

    def _search(self, sorted_vals, sorted_idx, leaf_of, w, stats, cand,
                Lp, impurity, task, min_records):
        F, R = self.feature_axis, self.row_axis
        fn_backend = splits.NUMERIC_BACKENDS[self.backend]

        def local(sv, si, cl, lf, ww, stt):
            # sv/si: (m_loc, n_loc) shard of the presorted order (GLOBAL
            # row ids); cl (T, m_loc, L+1); lf/ww (T, n); stt (T, n, S)
            # replicated — the paper's splitter memory layout ("Sliq/R and
            # DRF duplicate the class list in each worker").
            def per_tree(cl_t, lf_t, ww_t, st_t):
                def per_col(v, s, c):
                    lfs, wws, sts = lf_t[s], ww_t[s], st_t[s]
                    if R is None:
                        return fn_backend(v, lfs, wws, sts, c, Lp, impurity,
                                          task, min_records)
                    inbag = (wws > 0) & (lfs > 0)
                    contrib = jnp.where(inbag[:, None], sts, 0.0)
                    loc_tot = jax.ops.segment_sum(contrib, lfs,
                                                  num_segments=Lp + 1)
                    loc_last = jax.ops.segment_max(
                        jnp.where(inbag, v, -jnp.inf), lfs,
                        num_segments=Lp + 1)
                    all_tot = jax.lax.all_gather(loc_tot, R)   # (W, L+1, S)
                    all_last = jax.lax.all_gather(loc_last, R)  # (W, L+1)
                    r = jax.lax.axis_index(R)
                    W = all_tot.shape[0]
                    before = (jnp.arange(W) < r)[:, None, None]
                    h_init = jnp.sum(jnp.where(before, all_tot, 0.0), axis=0)
                    totals = jnp.sum(all_tot, axis=0)
                    v_init = jnp.max(jnp.where(before[..., 0], all_last,
                                               -jnp.inf), axis=0)
                    v_init = jnp.where(jnp.isfinite(v_init), v_init,
                                       jnp.inf)   # "none" sentinel
                    g, t = fn_backend(v, lfs, wws, sts, c, Lp, impurity,
                                      task, min_records, h_init=h_init,
                                      v_init=v_init, totals=totals)
                    # merge over row shards: max gain, ties -> earliest
                    # shard (the sequential scan order)
                    key = jnp.where(jnp.isfinite(g), g, -jnp.inf)
                    allg = jax.lax.all_gather(key, R)           # (W, L+1)
                    allt = jax.lax.all_gather(t, R)
                    win = jnp.argmax(allg, axis=0)
                    gsel = jnp.take_along_axis(allg, win[None], 0)[0]
                    tsel = jnp.take_along_axis(allt, win[None], 0)[0]
                    return gsel, tsel

                return jax.vmap(per_col)(sv, si, cl_t)

            return jax.vmap(per_tree)(cl, lf, ww, stt)

        sharded = _shmap(
            local, self.mesh,
            in_specs=(P(F, R), P(F, R), P(None, F, None),
                      P(None), P(None), P(None, None)),
            out_specs=(P(None, F, None), P(None, F, None)))
        return sharded(sorted_vals, sorted_idx, cand, leaf_of, w, stats)


# ---------------------------------------------------------------------------
# Histogram engine: psum of (bins × stats) tables
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedHistNumeric(_MeshEngine):
    """Approximate supersplit for `split_mode="hist"` (DESIGN.md §6).

    Columns shard over `feature_axis`; ROWS — plain row order, no presorted
    state — shard over `row_axis` together with the class list / bag
    weights / stats.  Each shard builds its local per-leaf (bin × stat)
    tables for ALL its columns in one flat scatter
    (`splits.feature_count_tables`, reading only the bit-packed bin cache)
    and a single `psum` per level merges them — the PLANET-style fixed-size
    merge vs the exact engine's resumable-scan all_gather.  Under
    `st.subtract` only the packed BUILD-slot tables cross the network
    ((ℓ/2+1)·B·S floats per column, ~half the plain payload); each shard
    then derives every sibling locally as parent − sibling from the
    replicated-in-spec carried tables.  `row_axis=None` gives the
    column-sharded-only variant (no psum).  Thresholds are reported as
    BIN INDICES (`bin_cut_thresholds`), decoded on the host.
    """

    needs_bins = True
    bin_cut_thresholds = True
    carries_tables = True
    supports_stream = True

    # -- streaming (DESIGN.md §8) -------------------------------------------
    # The accumulator keeps a leading row-shard axis R so `stream_accumulate`
    # is collective-FREE: each row shard adds its local chunk tables into
    # its own accumulator slice, and the level's single psum happens once
    # in `stream_finalize` — the same one-merge-per-level network profile
    # as the in-memory engine.

    def _acc_spec(self):
        return P(self.row_axis, None, self.feature_axis, None, None, None)

    def stream_init(self, T, st, Lp):
        from jax.sharding import NamedSharding
        S = st.num_classes if st.task == "classification" else 3
        R = self.row_shards()
        zeros = jnp.zeros((R, T, st.m_num, Lp + 1, S, st.num_bins),
                          jnp.float32)
        if self.row_axis is None:
            return zeros
        return jax.device_put(zeros, NamedSharding(self.mesh,
                                                   self._acc_spec()))

    def stream_accumulate(self, acc, bins, leaf, w, stats, labels, st, Lp):
        B = st.num_bins
        ids = _class_ids(labels, st)
        if self.row_axis is None:
            return acc + splits.feature_count_tables(
                bins, leaf, w, stats, Lp, B, labels=ids)[None]

        def local(a, bo, lf, ww, stt, *lb):
            # a (1, T, m_loc, L+1, S, B); bo (m_loc, c_loc); lf/ww (T, c_loc);
            # lb = (labels (c_loc,),) on the class path
            return a + splits.feature_count_tables(
                bo, lf, ww, stt, Lp, B, labels=lb[0] if lb else None)[None]

        F, R = self.feature_axis, self.row_axis
        in_specs = (self._acc_spec(), P(F, R), P(None, R), P(None, R),
                    P(None, R, None)) + ((P(R),) if ids is not None else ())
        args = (acc, bins, leaf, w, stats) + ((ids,) if ids is not None
                                              else ())
        return _shmap(local, self.mesh, in_specs=in_specs,
                      out_specs=self._acc_spec())(*args)

    def stream_finalize(self, acc):
        if self.row_axis is None:
            return acc[0]

        def merge(a):
            return jax.lax.psum(a[0], self.row_axis)

        return _shmap(merge, self.mesh, in_specs=(self._acc_spec(),),
                      out_specs=P(None, self.feature_axis, None, None,
                                  None))(acc)

    def table_updates(self, st, n, subtract):
        # no compaction: every row scatters, derive rows to slot 0
        return _scatter_updates(st, n)

    def supersplits(self, inp, st, Lp, cand):
        one = lambda x: None if x is None else x[None]
        res = self._search(inp.bin_of, one(inp.leaf_of), one(inp.w),
                           one(inp.stats), one(cand), Lp, st,
                           one(inp.prev_tables), one(inp.parent_of),
                           one(inp.sib_of), one(inp.slot_of), inp.labels)
        return tuple(r[0] for r in res)

    def supersplits_batched(self, inp, st, Lp, cand):
        return self._search(inp.bin_of, inp.leaf_of, inp.w, inp.stats,
                            cand, Lp, st, inp.prev_tables, inp.parent_of,
                            inp.sib_of, inp.slot_of, inp.labels)

    def __call__(self, bin_of, bin_edges, leaf_of, w, stats, cand, Lp,
                 impurity, task, min_records):
        """Legacy per-tree hist supersplit_fn signature (float thresholds,
        decoded here from the device-side edges for back-compat)."""
        from repro.core.level.engines import LevelStatics
        st = LevelStatics(m_num=bin_of.shape[0], m_cat=0, max_arity=1,
                          num_classes=stats.shape[-1],
                          num_bins=bin_edges.shape[-1], impurity=impurity,
                          task=task, min_records=min_records)
        # one_hot(label) · w rows: the argmax is the class wherever w > 0
        labels = (jnp.argmax(stats, axis=-1) if task == "classification"
                  else None)
        g, c = self._search(bin_of, leaf_of[None], w[None], stats[None],
                            cand[None], Lp, st, None, None, None, None,
                            labels)
        cuts = c[0].astype(jnp.int32)
        thr = jnp.take_along_axis(bin_edges, cuts, axis=1)
        return g[0], jnp.where(jnp.isfinite(g[0]), thr, 0.0)

    def _search(self, bin_of, leaf_of, w, stats, cand, Lp, st,
                prev_tables, parent_of, sib_of, slot_of, labels):
        F, R = self.feature_axis, self.row_axis
        B = st.num_bins
        subtract = st.subtract
        labels = _class_ids(labels, st)
        Wb = Lp // 2 + 1 if subtract else Lp + 1
        impurity, task, min_records = st.impurity, st.task, st.min_records

        def local(bo, cl, lf, ww, stt, *rest):
            # bo (m_loc, n_loc); cl (T, m_loc, L+1); lf/ww (T, n_loc);
            # stt (T, n_loc, S); rest = ([labels (n_loc,)] on the class
            # path, then prev (T, m_loc, Wprev, S, B), parent/sib/slot
            # (T, L+1) when subtracting)
            # NO row compaction here: the build-rows <= n/2 bound is
            # global, not per row shard — derive rows mask to slot 0
            lb, sub = ((rest[0], rest[1:]) if labels is not None
                       else (None, rest))
            with jax.named_scope("level.supersplit.tables"):
                if subtract:
                    prev, par, sib, slot = sub
                    ids = jax.vmap(lambda sl, l: sl[l])(slot, lf)
                else:
                    ids = lf
                packed = splits.feature_count_tables(bo, ids, ww, stt,
                                                     Wb - 1, B, labels=lb)
                if R is not None:
                    # THE merge: one psum of the (T, m_loc, Wb, S, B)
                    # tables — under subtraction only build slots cross
                    # the network
                    packed = jax.lax.psum(packed, R)
                if subtract:
                    tables = jax.vmap(
                        lambda pk, pv, pr, sb, sl:
                        _expand_subtracted(pk, pv, pr, sb, sl))(
                            packed, prev, par, sib, slot)
                else:
                    tables = packed

            def score(tb_t, cl_t):
                return jax.vmap(
                    lambda tb, c: splits.best_numeric_split_histogram(
                        tb, c, impurity, task, min_records))(tb_t, cl_t)
            with jax.named_scope("level.supersplit.score"):
                g, cuts = jax.vmap(score)(tables, cl)
            if st.carry_tables:
                return g, cuts, tables
            return g, cuts

        tab_spec = P(None, F, None, None, None)
        in_specs = [P(F, R), P(None, F, None), P(None, R), P(None, R),
                    P(None, R, None)]
        args = [bin_of, cand, leaf_of, w, stats]
        if labels is not None:
            in_specs.append(P(R))
            args.append(labels)
        if subtract:
            in_specs += [tab_spec, P(None, None), P(None, None),
                         P(None, None)]
            args += [prev_tables, parent_of, sib_of, slot_of]
        out_specs = (P(None, F, None), P(None, F, None))
        if st.carry_tables:
            out_specs = out_specs + (tab_spec,)
        sharded = _shmap(local, self.mesh,
                         in_specs=tuple(in_specs), out_specs=out_specs)
        return sharded(*args)


# ---------------------------------------------------------------------------
# Categorical engine: psum of (category × stats) tables
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedCategorical(_MeshEngine):
    """Exact categorical table engine under the mesh: the paper's
    'attribute value × class' count tables are built per row shard and
    merged by ONE psum of (L+1)·V·S floats per column (categorical tables
    are order-free, so the merge is exact); the Breiman-ordered prefix-cut
    scoring then runs replicated per column owner.  Requires m_cat
    divisible by the feature-axis size (pad columns or keep the local
    engine otherwise — `make_plan` defaults to local categoricals)."""

    kind = "categorical"

    def supersplits(self, inp, st, Lp, cand):
        g, m = self._search(inp.cat.T, inp.leaf_of[None], inp.w[None],
                            inp.stats[None], cand[None], Lp, st.max_arity,
                            st.impurity, st.task, st.min_records)
        return g[0], m[0]

    def supersplits_batched(self, inp, st, Lp, cand):
        return self._search(inp.cat.T, inp.leaf_of, inp.w, inp.stats, cand,
                            Lp, st.max_arity, st.impurity, st.task,
                            st.min_records)

    def _search(self, cat_cols, leaf_of, w, stats, cand, Lp, max_arity,
                impurity, task, min_records):
        F, R = self.feature_axis, self.row_axis

        def local(xc, cl, lf, ww, stt):
            def per_tree(cl_t, lf_t, ww_t, st_t):
                def per_col(x, c):
                    table = splits.categorical_count_table(
                        x, lf_t, ww_t, st_t, Lp, max_arity)
                    if R is not None:
                        table = jax.lax.psum(table, R)
                    return splits.best_categorical_split_from_table(
                        table, c, impurity, task, min_records)
                return jax.vmap(per_col)(xc, cl_t)
            return jax.vmap(per_tree)(cl, lf, ww, stt)

        sharded = _shmap(
            local, self.mesh,
            in_specs=(P(F, R), P(None, F, None), P(None, R), P(None, R),
                      P(None, R, None)),
            out_specs=(P(None, F, None), P(None, F, None, None)))
        return sharded(cat_cols, cand, leaf_of, w, stats)
