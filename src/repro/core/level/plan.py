"""LevelPlan: the one execution plan every training mode runs through.

A `LevelPlan` composes a numeric and a categorical `SplitEngine` with the
static level config, and lowers one whole depth level of Alg. 2 as a
single jitted device program (the plan is a static jit argument, so
choosing engines chooses a lowering):

    candidate draw → engine supersplits → cross-feature winner argmax →
    condition evaluation (step 5) → leaf reassignment (step 6) → next
    totals (+ the incremental leaf-order partition, DESIGN.md §2)

Two program shapes, both per depth level:

  * `_fused_level_step`          — one tree (tree.build_tree)
  * `_fused_level_step_batched`  — a whole tree batch (tree.build_forest,
    DESIGN.md §3): local engines run per tree inside the tree-axis vmap /
    lax.map; batch-native (mesh) engines run ONCE on the stacked state
    before it, so sharded training keeps the same D-dispatches-per-forest
    shape as local training.

The exact/hist × local/sharded mode matrix is therefore four engine
choices into ONE plan — not four code paths (DESIGN.md §7).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import bagging, splits
from repro.core.level.engines import (CategoricalTable, ExactNumeric,
                                      HistNumeric, LevelInputs, LevelStatics,
                                      SplitEngine)

# Dispatch and trace counts live in `repro.obs`: the tree.py drivers
# count dispatches (`level.dispatches`, `level.tree_dispatches`,
# `stream.chunk_dispatches`), the programs below count their traces
# (`level.traces`, `stream.traces`, `stream.score_traces`).  Phases of the
# programs carry `jax.named_scope` names (`level.draw`, ...; see obs).

# Above this many row-state elements (T·m_num·n) the batched level step
# switches from vmap (SIMD across trees) to lax.map (sequential trees, one
# program) — the vmapped stack stops being cache-resident and measures
# ~1.5x slower on CPU.  The canonical (monkeypatchable) knob lives in
# tree.py as `_BATCH_VMAP_ELEMS`; this is its default.
_BATCH_VMAP_ELEMS_DEFAULT = 1 << 19


def _batch_vmap_elems() -> int:
    from repro.core import tree as _tree      # late: tree.py imports us
    return getattr(_tree, "_BATCH_VMAP_ELEMS", _BATCH_VMAP_ELEMS_DEFAULT)


def _pad_leaves(L: int, pad: int) -> int:
    """Pad to a power of two (recompilation count is O(log leaves))."""
    return max(pad, 1 << (L - 1).bit_length())


@functools.partial(jax.jit, static_argnames=("Lp",))
def _leaf_totals(leaf_of, stats, w, Lp):
    inbag = (w > 0) & (leaf_of > 0)
    return jax.ops.segment_sum(jnp.where(inbag[:, None], stats, 0.0),
                               leaf_of, num_segments=Lp + 1)


@dataclasses.dataclass(frozen=True)
class LevelPlan:
    """Engines + static config; hashable, a static arg of the fused jits."""
    numeric: Optional[SplitEngine]
    categorical: Optional[SplitEngine]
    m_num: int
    m_cat: int
    max_arity: int
    num_classes: int
    m_prime: int
    usb: bool
    num_bins: int
    impurity: str
    task: str
    min_records: float
    hist_subtract: bool = True

    @property
    def statics(self) -> LevelStatics:
        return LevelStatics(
            m_num=self.m_num, m_cat=self.m_cat, max_arity=self.max_arity,
            num_classes=self.num_classes, num_bins=self.num_bins,
            impurity=self.impurity, task=self.task,
            min_records=self.min_records)

    @property
    def use_ord(self) -> bool:
        """Drivers maintain the incremental leaf order for this plan."""
        return bool(self.m_num) and self.numeric is not None \
            and self.numeric.uses_ord

    @property
    def pass_sorted(self) -> bool:
        """The level step reads sorted_vals/sorted_idx (vs zero dummies)."""
        return bool(self.m_num) and self.numeric.needs_sorted \
            and not self.use_ord

    @property
    def use_bin_cuts(self) -> bool:
        """The numeric engine reports BIN INDICES, not float thresholds:
        condition evaluation runs on the bit-packed bin cache and the host
        decodes thresholds from the (host-side) float edges — no float32
        column and no edge array inside the level program (DESIGN.md §6).
        """
        return bool(self.m_num) and self.numeric is not None \
            and self.numeric.bin_cut_thresholds

    @property
    def pass_num(self) -> bool:
        """The level step reads the raw float numeric columns (vs zero
        dummies) — every mode except the bin-cache hist fast path."""
        return bool(self.m_num) and not self.use_bin_cuts

    @property
    def pass_edges(self) -> bool:
        """The level step reads the float bucket edges on DEVICE — only
        legacy hist closures (LegacyFn), which score and return float
        thresholds themselves."""
        return bool(self.m_num) and self.numeric is not None \
            and self.numeric.needs_bins and not self.use_bin_cuts

    @property
    def carries_tables(self) -> bool:
        """Histogram subtraction is on: the level loop carries each
        level's merged per-leaf tables and every level builds only the
        smaller child of each split, deriving the sibling as
        parent − sibling.  Classification only: its table entries are
        integer-valued bag counts, so the subtraction is EXACT (bit-equal
        to a plain rebuild, which tests assert); regression tables hold
        float y-sums whose subtraction could drift in the last ulp, so
        regression always rebuilds plain.
        """
        return self.use_bin_cuts and self.numeric.carries_tables \
            and self.hist_subtract and self.task == "classification"

    def table_updates(self, n: int, subtract: bool) -> int:
        """Scatter updates one tree's bin-table build makes at a level of
        n rows (`SplitEngine.table_updates`; 0 without numeric columns)."""
        return (self.numeric.table_updates(self.statics, n, subtract)
                if self.m_num else 0)

    @property
    def row_shards(self) -> int:
        """Row-shard count n must stay divisible by (device pruning).

        Both engines constrain it (a sharded categorical engine can ride a
        local numeric one), so the bound is their lcm.
        """
        return math.lcm(
            self.numeric.row_shards() if self.numeric is not None else 1,
            self.categorical.row_shards() if self.categorical is not None
            else 1)


def make_plan(params, *, m_num: int, m_cat: int, max_arity: int,
              num_classes: int, m_prime: int,
              engine: Optional[SplitEngine] = None,
              cat_engine: Optional[SplitEngine] = None) -> LevelPlan:
    """Resolve a LevelPlan from TreeParams + optional engine overrides.

    Defaults: the local engine for `params.split_mode` on
    `params.backend`, local categorical tables.  A numeric `engine` must
    match the split mode (a hist engine scores bucket boundaries, an exact
    engine needs the presorted order).
    """
    hist = params.split_mode == "hist"
    if engine is None:
        engine = (HistNumeric(params.backend) if hist
                  else ExactNumeric(params.backend))
    elif engine.kind != "numeric":
        raise ValueError(f"numeric engine expected, got {engine!r}")
    elif hist and not engine.needs_bins:
        raise ValueError(
            f"split_mode='hist' needs a histogram engine, got {engine!r}")
    elif not hist and engine.needs_bins:
        raise ValueError(
            f"split_mode='exact' cannot use histogram engine {engine!r}")
    if cat_engine is None:
        cat_engine = CategoricalTable(params.backend)
    elif cat_engine.kind != "categorical":
        raise ValueError(f"categorical engine expected, got {cat_engine!r}")
    return LevelPlan(
        numeric=engine if m_num else None,
        categorical=cat_engine if m_cat else None,
        m_num=m_num, m_cat=m_cat, max_arity=max_arity,
        num_classes=num_classes, m_prime=m_prime, usb=params.usb,
        num_bins=params.num_bins, impurity=params.impurity,
        task=params.task, min_records=params.min_records,
        hist_subtract=getattr(params, "hist_subtract", True))


# ---------------------------------------------------------------------------
# The fused level step (one jitted device program per depth)
# ---------------------------------------------------------------------------

def _partition_leaf_order(ord_idx, lf_pos, bits, new_left, new_right,
                          row_counts, key_counts):
    """Advance the per-column (leaf, value)-sorted order to the next level.

    Children occupy consecutive id ranges in parent order (left id <
    right id, parents in id order, closed = 0), so the stable counting sort
    by the NEW leaf id reduces to: closed rows to the front (stable), then
    a stable left/right partition inside each parent's contiguous block —
    O(n) work with ONE cumsum and ONE scatter per column, no sort.
    Relative row order inside every child equals the parent's
    (value-ascending), exactly what a stable sort would produce, so the
    `segment` backend's summation order — and hence its float results —
    are preserved bit-for-bit.

    The block structure is column-independent (same leaf histogram in every
    column), so everything except the row permutation itself — `lf_pos`,
    the current `row_counts` (L+1,) and next-level `key_counts` (2L+1,)
    histograms, block starts, target offsets — is computed once.  Only the
    1-bit condition outcome `bits` (row-indexed) is gathered per column.

    Accepts an optional LEADING TREE AXIS on every argument
    (ord_idx (T, m, n), the rest (T, ...)): the batched level step calls it
    this way, outside its tree-axis vmap, so the permutation lands in ONE
    flat scatter over all T·m columns — XLA lowers a batched-operand
    scatter (what vmap would produce) far slower than the same scatter on a
    flattened index space (~2x on CPU, measured).  The per-tree call takes
    the same flat-scatter path with T = 1.
    """
    batched = ord_idx.ndim == 3
    if not batched:
        ord_idx, lf_pos, bits = ord_idx[None], lf_pos[None], bits[None]
        new_left, new_right = new_left[None], new_right[None]
        row_counts, key_counts = row_counts[None], key_counts[None]
    B, m, n = ord_idx.shape

    def shared(lf_pos, new_left, new_right, row_counts, key_counts):
        # parents either split wholly or close wholly, so a block is
        # all-closed or all-left/right; closed rows keep their block order,
        # preceded by the closed rows of earlier parents
        parent_closed = new_left == 0                         # (Lp+1,)
        closed_sizes = jnp.where(parent_closed, row_counts, 0)
        closed_before = jnp.cumsum(closed_sizes) - closed_sizes
        offs = jnp.cumsum(key_counts) - key_counts            # per new key
        is_start = jnp.concatenate(
            [jnp.ones((1,), bool), lf_pos[1:] != lf_pos[:-1]])
        start_idx = jax.lax.cummax(jnp.where(is_start, jnp.arange(n), -1))
        in_block = jnp.arange(n) - start_idx                  # rank in block
        return (start_idx, in_block, parent_closed[lf_pos],
                closed_before[lf_pos] + in_block,             # (n,) shared
                offs[new_left[lf_pos]], offs[new_right[lf_pos]])

    start_idx, in_block, closed_here, pos_closed, offs_l, offs_r = \
        jax.vmap(shared)(lf_pos, new_left, new_right, row_counts, key_counts)

    wl = jax.vmap(lambda b, oi: b[oi])(                       # went LEFT
        bits, ord_idx.reshape(B, m * n)).reshape(B, m, n)
    cl = jnp.cumsum(wl.astype(jnp.int32), axis=2) - wl
    si = jnp.broadcast_to(start_idx[:, None, :], (B, m, n))
    left_rank = cl - jnp.take_along_axis(cl, si, axis=2)
    pos = jnp.where(
        closed_here[:, None, :], pos_closed[:, None, :],
        jnp.where(wl, offs_l[:, None, :] + left_rank,
                  offs_r[:, None, :] + in_block[:, None, :] - left_rank))
    if B * m * n < 2 ** 31:
        base = (jnp.arange(B * m, dtype=jnp.int32) * n).reshape(B, m, 1)
        out = jnp.zeros((B * m * n,), ord_idx.dtype).at[
            (pos + base).reshape(-1)].set(ord_idx.reshape(-1),
                                          unique_indices=True
                                          ).reshape(B, m, n)
    else:
        # the flat index space would overflow int32 (x64 is off); fall back
        # to per-column scatters, whose indices stay < n
        out = jax.vmap(jax.vmap(
            lambda p, o: jnp.zeros_like(o).at[p].set(
                o, unique_indices=True)))(pos, ord_idx)
    return out if batched else out[0]


def _eval_conditions_core(num, cat, leaf_of, feat_of_leaf, thr_of_leaf,
                          iscat_of_leaf, mask_of_leaf, m_num, bin_of=None):
    """Alg. 2 step 5: evaluate the winning condition of each sample's leaf.

    Returns bits (n,) bool — True = LEFT.  In the distributed engine this is
    the 1-bit-per-sample payload that gets allreduced (see distributed.py).

    When `bin_of` is given (the hist fast path, plan.use_bin_cuts) the
    numeric condition is evaluated on the bit-packed bin cache instead of
    the float columns: `thr_of_leaf` then holds the winning BIN INDEX and
    `bin <= cut  <=>  x <= edges[cut]` (presort.quantize_edges), so the
    partition is identical while the program never reads float32 columns.
    `num` is feature-major, (m_num, n): see `tree._level_num`.
    """
    f = feat_of_leaf[leaf_of]                                   # (n,)
    jn = jnp.clip(f, 0, max(m_num - 1, 0))
    jc = jnp.clip(f - m_num, 0, max(cat.shape[1] - 1, 0))
    if bin_of is not None and bin_of.size:
        xbin = bin_of[jn, jnp.arange(leaf_of.shape[0])].astype(jnp.int32)
        num_bit = xbin <= thr_of_leaf[leaf_of].astype(jnp.int32)
    else:
        xnum = (num[jn, jnp.arange(leaf_of.shape[0])]
                if num.size else jnp.zeros_like(leaf_of, jnp.float32))
        num_bit = xnum <= thr_of_leaf[leaf_of]
    xcat = jnp.take_along_axis(cat, jc[:, None], axis=1)[:, 0] if cat.size else jnp.zeros_like(leaf_of)
    cat_bit = mask_of_leaf[leaf_of, xcat]
    return jnp.where(iscat_of_leaf[leaf_of], cat_bit, num_bit)


def _candidates(fkey, depth, splittable_p, Lp, plan):
    """Per-leaf candidate mask (m, L+1), leaf 0 and unsplittable rows False.

    One tree.  Deterministic in (fkey, depth, leaf row): the batched step
    recomputes the identical mask outside the vmap for batch-native
    engines (`_candidates_batched`) — same fold_in chain, bit-identical.
    """
    m = plan.m_num + plan.m_cat
    cand = bagging.candidate_features(fkey, depth, Lp, m, plan.m_prime,
                                      plan.usb)
    cand = cand & splittable_p[1:, None]
    return jnp.concatenate([jnp.zeros((1, m), bool), cand], 0)   # (L+1, m)


def _candidates_batched(fkeys, depth, splittable_p, Lp, plan):
    """(T, m, L+1) candidate masks for the whole batch."""
    def per_tree(fk, sp):
        return _candidates(fk, depth, sp, Lp, plan).T
    return jax.vmap(per_tree)(fkeys, splittable_p)


def _level_step_core(num, cat, labels, sorted_vals, sorted_idx, bin_of,
                     bin_edges, ord_idx, leaf_of, w, stats, splittable_p,
                     totals, row_counts, prev_tables, parent_of, sib_of,
                     slot_of, fkey, depth, *, plan, Lp, need_partition,
                     subtract=False, fused_tail=True, pre_num=None,
                     pre_cat=None, pre_tables=None):
    """One whole depth level of Alg. 2 as a single device program.

    Steps 3-7 fused: candidate feature draw, numeric + categorical engine
    supersplits, partial-supersplit merge (cross-feature argmax), condition
    evaluation, leaf reassignment, and the next level's leaf totals.  Only
    the returned per-leaf struct (winning feature, gain, threshold,
    category mask, split bitmap) is fetched by the host; the row-indexed
    state (`leaf_of`, the per-column leaf order) stays device-resident —
    as do the carried histogram tables when the plan runs the subtraction
    recurrence (`prev_tables` + the parent/sib/slot maps; `subtract` is
    the static saying they are valid this level, i.e. not the root).

    `pre_num`/`pre_cat` carry the (gains, thresholds/masks) a batch-native
    engine already computed for this tree OUTSIDE the tree-axis vmap; when
    given, the corresponding engine is not called here (`pre_tables` are
    the new carried tables it returned alongside).
    """
    m_num, m_cat = plan.m_num, plan.m_cat
    L1 = Lp + 1
    n = leaf_of.shape[0]

    # Alg. 2 step 3: seeded per-leaf candidate features (paper §2.2/§2.4)
    with jax.named_scope("level.draw"):
        cand_p = _candidates(fkey, depth, splittable_p, Lp, plan)

    inp = LevelInputs(num=num, cat=cat, labels=labels,
                      sorted_vals=sorted_vals, sorted_idx=sorted_idx,
                      bin_of=bin_of, bin_edges=bin_edges, ord_idx=ord_idx,
                      leaf_of=leaf_of, w=w, stats=stats, totals=totals,
                      row_counts=row_counts, prev_tables=prev_tables,
                      parent_of=parent_of, sib_of=sib_of, slot_of=slot_of)
    carries = plan.carries_tables
    statics = plan.statics._replace(carry_tables=carries, subtract=subtract)

    gains_parts, masks = [], None
    new_tables = pre_tables
    thr_num = jnp.zeros((max(m_num, 1), L1), jnp.float32)
    with jax.named_scope("level.supersplit"):
        if m_num:
            if pre_num is not None:
                g, t = pre_num
            else:
                res = plan.numeric.supersplits(inp, statics, Lp,
                                               cand_p[:, :m_num].T)
                if carries:
                    g, t, new_tables = res
                else:
                    g, t = res
            gains_parts.append(g)
            thr_num = t
        if m_cat:
            if pre_cat is not None:
                g, masks = pre_cat
            else:
                g, masks = plan.categorical.supersplits(inp, statics, Lp,
                                                        cand_p[:, m_num:].T)
            gains_parts.append(g)

    with jax.named_scope("level.merge"):
        all_gains = jnp.concatenate(gains_parts, axis=0)        # (m, L1)

        # tree builder merges partial supersplits (Alg. 2 step 3, argmax)
        best_feat = jnp.argmax(all_gains, axis=0).astype(jnp.int32)
        best_gain = jnp.take_along_axis(all_gains, best_feat[None], 0)[0]
        will_split = (splittable_p & jnp.isfinite(best_gain)
                      & (best_gain > 1e-9))

        # children get consecutive 1-based ids in leaf order (step 6)
        ks = jnp.cumsum(will_split.astype(jnp.int32))
        new_left = jnp.where(will_split, 2 * ks - 1, 0).astype(jnp.int32)
        new_right = jnp.where(will_split, 2 * ks, 0).astype(jnp.int32)

        feat_of_leaf = jnp.where(will_split, best_feat, 0).astype(jnp.int32)
        iscat_of_leaf = will_split & (best_feat >= m_num) if m_cat else \
            jnp.zeros((L1,), bool)
        thr_sel = jnp.take_along_axis(
            thr_num, jnp.clip(best_feat, 0, max(m_num - 1, 0))[None], 0)[0]
        thr_of_leaf = jnp.where(will_split & ~iscat_of_leaf, thr_sel, 0.0)
        if m_cat:
            jc = jnp.clip(best_feat - m_num, 0, m_cat - 1)
            mask_sel = masks[jc, jnp.arange(L1)]                # (L1, V)
            mask_of_leaf = jnp.where(iscat_of_leaf[:, None], mask_sel,
                                     False)
        else:
            mask_of_leaf = jnp.zeros((L1, plan.max_arity), bool)

    # Alg. 2 steps 5-6: 1-bit condition per sample, reassign to children
    with jax.named_scope("level.reassign"):
        bits = _eval_conditions_core(num, cat, leaf_of, feat_of_leaf,
                                     thr_of_leaf, iscat_of_leaf,
                                     mask_of_leaf, m_num,
                                     bin_of=bin_of if plan.use_bin_cuts
                                     else None)
        new_leaf_of = jnp.where(
            leaf_of > 0,
            jnp.where(bits, new_left[leaf_of], new_right[leaf_of]), 0)

    use_ord = plan.use_ord
    struct = {"best_feat": best_feat, "best_gain": best_gain,
              "thr": thr_of_leaf, "mask": mask_of_leaf,
              "will_split": will_split}
    if not fused_tail:
        # batched mode: the scatter-backed reductions (next totals, key
        # counts, order partition) run OUTSIDE the tree-axis vmap, on a
        # flattened (tree, segment) index space — vmap would lower them as
        # batched-operand scatters, ~2x slower on CPU.  Hand back the
        # per-tree pieces the wrapper needs.
        part = (bits, new_left, new_right) if use_ord else None
        return struct, new_leaf_of, ord_idx, None, part, new_tables

    # next-level totals (node values / counts / splittable for depth+1)
    with jax.named_scope("level.totals"):
        inb = (w > 0) & (new_leaf_of > 0)
        next_totals = jax.ops.segment_sum(
            jnp.where(inb[:, None], stats, 0.0), new_leaf_of,
            num_segments=2 * Lp + 1)

        if use_ord or carries:
            # next level's per-child row counts: the ord layout's
            # row_counts, and (subtraction) what the host uses to pick
            # each split's SMALLER child as the build leaf
            key_counts = jax.ops.segment_sum(
                jnp.ones((n,), jnp.int32), new_leaf_of,
                num_segments=2 * Lp + 1)
            struct["key_counts"] = key_counts
    if use_ord and need_partition:
        with jax.named_scope("level.partition"):
            lf_pos = leaf_of[ord_idx[0]]
            new_ord_idx = _partition_leaf_order(
                ord_idx, lf_pos, bits, new_left, new_right, row_counts,
                key_counts)
    else:   # no ord layout, or the next level cannot split (max depth)
        new_ord_idx = ord_idx
    return struct, new_leaf_of, new_ord_idx, next_totals, None, new_tables


_LEVEL_STATICS = ("plan", "Lp", "need_partition", "subtract")


@functools.partial(jax.jit, static_argnames=_LEVEL_STATICS)
def _fused_level_step(num, cat, labels, sorted_vals, sorted_idx, bin_of,
                      bin_edges, ord_idx, leaf_of, w, stats, splittable_p,
                      totals, row_counts, prev_tables, parent_of, sib_of,
                      slot_of, fkey, depth, *, plan, Lp, need_partition,
                      subtract=False):
    """The per-tree fused level step (see `_level_step_core`)."""
    obs.count("level.traces")
    struct, new_leaf_of, new_ord_idx, next_totals, _, new_tables = \
        _level_step_core(
            num, cat, labels, sorted_vals, sorted_idx, bin_of, bin_edges,
            ord_idx, leaf_of, w, stats, splittable_p, totals, row_counts,
            prev_tables, parent_of, sib_of, slot_of, fkey, depth, plan=plan,
            Lp=Lp, need_partition=need_partition, subtract=subtract)
    return struct, new_leaf_of, new_ord_idx, next_totals, new_tables


@functools.partial(jax.jit, static_argnames=_LEVEL_STATICS)
def _fused_level_step_batched(num, cat, labels, sorted_vals, sorted_idx,
                              bin_of, bin_edges, ord_idx, leaf_of, w, stats,
                              splittable_p, totals, row_counts, prev_tables,
                              parent_of, sib_of, slot_of, fkeys, depth,
                              *, plan, Lp, need_partition, subtract=False):
    """One depth level of EVERY tree in a batch as a single device program.

    Trees are independent, so the whole fused level step — candidate draw,
    numeric + categorical supersplit, winner argmax, condition evaluation,
    leaf reassignment, next-level totals, incremental leaf-order partition —
    is `vmap`ped over a leading tree axis T.  Shared read-only inputs (the
    raw columns, labels, the forest-wide presorted order, the bucket
    state) broadcast; the per-tree state batches:

        num (n, m_num), cat (n, m_cat), labels (n,),
        sorted_vals/sorted_idx (m_num, n), bin_of/bin_edges  [shared]
        ord_idx (T, m_num, n), leaf_of (T, n), w (T, n), stats (T, n, S),
        splittable_p (T, Lp+1), totals (T, Lp+1, S), row_counts (T, Lp+1),
        fkeys (T, key)                                       [batched]

    `Lp` is the batch-wide padded frontier width (max over the batch's
    trees); trees with fewer open leaves — or none, having finished early —
    are masked through `splittable_p`, which zeroes their candidate sets so
    every gain is −inf and `will_split` stays False.  Because
    `bagging.candidate_features` is padding-independent (per-leaf fold-in),
    batching under the shared `Lp` is bit-identical per tree to the
    per-tree `_fused_level_step` under that tree's own padding — the
    property tests/test_forest_batch.py asserts against the reference
    builder.  The Pallas paths (`split_scan`, `feat_hist`) batch through
    `pallas_call`'s vmap rule, which folds the tree axis into the kernel
    grid — still one device program.

    BATCH-NATIVE engines (the mesh-sharded ones) are called once, here,
    on the stacked (T, ...) state BEFORE the tree-axis vmap — shard_map
    composes with an explicit leading batch axis, not with a vmap batching
    rule — and their per-tree (gains, thresholds/masks) slices flow into
    the vmapped core as `pre_num`/`pre_cat`.  Sharded training therefore
    inherits the tree batch, the early-finish masking and the flat-scatter
    tail with no special-cased host loop.

    Two lowering strategies, chosen statically by batch working-set size
    (`tree._BATCH_VMAP_ELEMS`):

      * SIMD across trees (`vmap` of the core, scatters flattened over the
        (tree, segment) index space) when the batch's row state is
        cache-resident — the fast path at small n, where dispatch overhead
        dominates and cross-tree vectorization is free;
      * sequential trees (`lax.map` of the per-tree core) when the stacked
        state would thrash cache (measured ~1.5x slower under vmap on CPU
        at T=16, n=100k) — still ONE device program per level, so the
        T·D → D dispatch/host-sync amortization is kept at every size.

    Returns the per-tree struct dict and next-level state, all with the
    leading T axis; the host fetches the structs in ONE transfer per level.
    """
    obs.count("level.traces")
    T, n = leaf_of.shape
    m_num, m_cat = plan.m_num, plan.m_cat
    use_ord = plan.use_ord
    carries = plan.carries_tables

    # batch-native (mesh) engines: one sharded search for the whole batch
    pres: list = []
    pre_tables = None
    has_pre_num = bool(m_num) and plan.numeric.batch_native
    has_pre_cat = bool(m_cat) and plan.categorical.batch_native
    if has_pre_num or has_pre_cat:
        with jax.named_scope("level.draw"):
            cand_b = _candidates_batched(fkeys, depth, splittable_p, Lp,
                                         plan)
        inp_b = LevelInputs(num=num, cat=cat, labels=labels,
                            sorted_vals=sorted_vals, sorted_idx=sorted_idx,
                            bin_of=bin_of, bin_edges=bin_edges,
                            ord_idx=ord_idx, leaf_of=leaf_of, w=w,
                            stats=stats, totals=totals,
                            row_counts=row_counts, prev_tables=prev_tables,
                            parent_of=parent_of, sib_of=sib_of,
                            slot_of=slot_of)
        statics_b = plan.statics._replace(carry_tables=carries,
                                          subtract=subtract)
        with jax.named_scope("level.supersplit"):
            if has_pre_num:
                res = plan.numeric.supersplits_batched(
                    inp_b, statics_b, Lp, cand_b[:, :m_num])
                if carries:
                    pre_tables = res[2]  # carried OUTSIDE the tree vmap
                    res = res[:2]
                pres += list(res)
            if has_pre_cat:
                pres += list(plan.categorical.supersplits_batched(
                    inp_b, statics_b, Lp, cand_b[:, m_num:]))

    def _unpack_pre(rest):
        pn = pc = None
        if has_pre_num:
            pn, rest = (rest[0], rest[1]), rest[2:]
        if has_pre_cat:
            pc = (rest[0], rest[1])
        return pn, pc

    if T * max(m_num, 1) * n > _batch_vmap_elems():
        # cache-bound regime: run the trees sequentially INSIDE the program
        def body(args):
            (ord_t, leaf_t, w_t, stats_t, sp_t, tot_t, rc_t, pt_t, par_t,
             sib_t, slot_t, fk_t) = args[:12]
            pn, pc = _unpack_pre(args[12:])
            s, nl, no, nt, _, ntab = _level_step_core(
                num, cat, labels, sorted_vals, sorted_idx, bin_of,
                bin_edges, ord_t, leaf_t, w_t, stats_t, sp_t, tot_t, rc_t,
                pt_t, par_t, sib_t, slot_t, fk_t, depth, plan=plan, Lp=Lp,
                need_partition=need_partition, subtract=subtract,
                fused_tail=True, pre_num=pn, pre_cat=pc)
            return s, nl, no, nt, ntab

        # the loop's own slicing and stacking of each tree's state
        with jax.named_scope("level.tree_loop"):
            struct, new_leaf_of, new_ord_idx, next_totals, new_tables = \
                jax.lax.map(
                    body, tuple([ord_idx, leaf_of, w, stats, splittable_p,
                                 totals, row_counts, prev_tables, parent_of,
                                 sib_of, slot_of, fkeys] + pres))
        if pre_tables is not None:
            new_tables = pre_tables
        # rows closed in EVERY tree: the (free) batched-pruning trigger —
        # the driver reads it from the fetched struct instead of issuing a
        # separate reduction + host sync per level
        with jax.named_scope("level.totals"):
            struct = dict(struct, closed_rows=jnp.sum(
                ~(new_leaf_of > 0).any(axis=0)))
        return struct, new_leaf_of, new_ord_idx, next_totals, new_tables

    def vcore(num, cat, labels, sorted_vals, sorted_idx, bin_of, bin_edges,
              ord_t, leaf_t, w_t, stats_t, sp_t, tot_t, rc_t, pt_t, par_t,
              sib_t, slot_t, fk_t, depth, *rest):
        pn, pc = _unpack_pre(rest)
        return _level_step_core(
            num, cat, labels, sorted_vals, sorted_idx, bin_of, bin_edges,
            ord_t, leaf_t, w_t, stats_t, sp_t, tot_t, rc_t, pt_t, par_t,
            sib_t, slot_t, fk_t, depth, plan=plan, Lp=Lp,
            need_partition=need_partition, subtract=subtract,
            fused_tail=False, pre_num=pn, pre_cat=pc)

    in_axes = tuple([None] * 7 + [0] * 12 + [None] + [0] * len(pres))
    struct, new_leaf_of, _, _, part, new_tables = \
        jax.vmap(vcore, in_axes=in_axes)(
            num, cat, labels, sorted_vals, sorted_idx, bin_of, bin_edges,
            ord_idx, leaf_of, w, stats, splittable_p, totals, row_counts,
            prev_tables, parent_of, sib_of, slot_of, fkeys, depth, *pres)
    if pre_tables is not None:
        new_tables = pre_tables

    # scatter-backed tail on the FLAT (tree, segment) index space: per-tree
    # results are bit-identical (each tree's rows accumulate in the same
    # order as in the per-tree program) but the scatters lower ~2x faster
    # than their vmapped form on CPU
    with jax.named_scope("level.totals"):
        struct = dict(struct, closed_rows=jnp.sum(  # see the map branch
            ~(new_leaf_of > 0).any(axis=0)))
        L2 = 2 * Lp + 1
        flat_ids = (new_leaf_of + jnp.arange(T, dtype=jnp.int32)[:, None]
                    * L2).reshape(-1)
        inb = (w > 0) & (new_leaf_of > 0)
        next_totals = jax.ops.segment_sum(
            jnp.where(inb.reshape(-1)[:, None], stats.reshape(T * n, -1),
                      0.0),
            flat_ids, num_segments=T * L2).reshape(T, L2, -1)
        if use_ord or carries:
            key_counts = jax.ops.segment_sum(
                jnp.ones((T * n,), jnp.int32), flat_ids,
                num_segments=T * L2).reshape(T, L2)
            struct = dict(struct, key_counts=key_counts)
    if use_ord and need_partition:
        with jax.named_scope("level.partition"):
            bits, new_left, new_right = part
            lf_pos = jax.vmap(lambda lf, oi: lf[oi])(leaf_of, ord_idx[:, 0])
            new_ord_idx = _partition_leaf_order(
                ord_idx, lf_pos, bits, new_left, new_right, row_counts,
                key_counts)
    else:
        new_ord_idx = ord_idx
    return struct, new_leaf_of, new_ord_idx, next_totals, new_tables


# ---------------------------------------------------------------------------
# Out-of-core streaming level steps (DESIGN.md §8)
# ---------------------------------------------------------------------------
#
# `tree.build_forest_streamed` splits the fused level step into three
# jitted programs so the n-sized state never has to exist on device:
#
#   _stream_chunk_step     per chunk: replay the PREVIOUS level's winning
#                          conditions on the chunk's bin block (the same
#                          `_eval_conditions_core` bin fast path),
#                          recompute row stats, and fold the chunk into
#                          the engine's table accumulator.  Statics are
#                          (plan, Lp, Lpp, root, need_tables) — the padded
#                          widths change O(log L) times per fit, so chunk
#                          iteration NEVER retraces per chunk.
#   _stream_finalize_step  per level: merge the accumulator (the sharded
#                          engine's one psum) and reduce the per-leaf
#                          totals the host reads for node values.
#   _stream_score_step     per level: candidate draw + histogram scoring +
#                          the EXACT `_level_step_core` winner/child-id
#                          formulas, on (T, m, L+1, S, B) tables alone —
#                          engine-independent, no row state.
#
# Classification tables are integer-valued f32, so the chunked
# accumulation is bit-equal to the single-pass scatter; everything
# downstream of the tables is shared arithmetic with the in-memory path,
# which is what makes streamed fits node-for-node identical.

_STREAM_CHUNK_STATICS = ("plan", "Lp", "Lpp", "root", "need_tables")


@functools.partial(jax.jit, static_argnames=_STREAM_CHUNK_STATICS)
def _stream_chunk_step(bins_c, labels_c, w_c, leaf_prev_c, feat_of_leaf,
                       cut_of_leaf, new_left, new_right, tables, *,
                       plan, Lp, Lpp, root, need_tables):
    """Fold one fixed-shape row chunk into the level accumulator.

    bins_c (m_num, c) packed; labels_c (c,); w_c/leaf_prev_c (T, c);
    feat_of_leaf/cut_of_leaf/new_left/new_right (T, Lpp+1) — the previous
    level's decisions (unused when `root`).  Returns (leaf_c (T, c) — the
    chunk's CURRENT-level leaf ids, fetched back to the host-resident
    assignment — and the updated accumulator).  Padding rows ride with
    w = 0 and leaf_prev = 0: they stay closed and contribute zero.
    """
    obs.count("stream.traces")
    c = labels_c.shape[0]
    statics = plan.statics

    if root:
        leaf_c = leaf_prev_c
    else:
        def reassign(lf, feat, cut, nl, nr):
            jn = jnp.clip(feat[lf], 0, max(plan.m_num - 1, 0))
            xbin = bins_c[jn, jnp.arange(c)].astype(jnp.int32)
            bit = xbin <= cut[lf].astype(jnp.int32)
            return jnp.where(lf > 0, jnp.where(bit, nl[lf], nr[lf]), 0)
        with jax.named_scope("level.reassign"):
            leaf_c = jax.vmap(reassign)(leaf_prev_c, feat_of_leaf,
                                        cut_of_leaf, new_left, new_right)

    stats_c = jax.vmap(lambda ww: splits.row_stats(
        labels_c, ww, plan.num_classes, plan.task))(w_c)
    if need_tables:
        with jax.named_scope("level.supersplit.tables"):
            tables = plan.numeric.stream_accumulate(
                tables, bins_c, leaf_c, w_c, stats_c, labels_c, statics, Lp)
    else:
        # final level: no more splits to score — accumulate only the
        # per-leaf stat totals (T, Lp+1, S) for the node values
        def tot(lf, ww, stt):
            inb = (ww > 0) & (lf > 0)
            return jax.ops.segment_sum(jnp.where(inb[:, None], stt, 0.0),
                                       lf, num_segments=Lp + 1)
        with jax.named_scope("level.totals"):
            tables = tables + jax.vmap(tot)(leaf_c, w_c, stats_c)
    return leaf_c, tables


@functools.partial(jax.jit, static_argnames=("plan",))
def _stream_finalize_step(tables, *, plan):
    """Merge the chunk accumulator and reduce per-leaf totals.

    Returns (merged (T, m, L+1, S, B) tables, totals (T, L+1, S)).  The
    totals come from feature 0's table summed over bins — for integer
    classification stats this equals the direct per-row segment_sum
    bit-for-bit (every in-bag row lands in exactly one bin)."""
    merged = plan.numeric.stream_finalize(tables)
    return merged, merged[:, 0].sum(axis=3)


@functools.partial(jax.jit, static_argnames=("plan", "Lp"))
def _stream_score_step(tables, splittable_p, fkeys, depth, *, plan, Lp):
    """Score one level from merged tables: `_level_step_core`'s candidate
    draw → histogram scoring → winner argmax → child-id assignment, with
    no row state (numeric hist only, so the m_cat branches drop out).
    Returns the per-tree decision struct; `thr` holds winning BIN INDICES
    (plan.use_bin_cuts) and `new_left`/`new_right`/`feat_of_leaf` feed the
    next level's chunk reassignment."""
    obs.count("stream.score_traces")

    def per_tree(tb, sp, fk):
        with jax.named_scope("level.draw"):
            cand_p = _candidates(fk, depth, sp, Lp, plan)       # (L+1, m)
        with jax.named_scope("level.supersplit.score"):
            g, cuts = jax.vmap(
                lambda t, cd: splits.best_numeric_split_histogram(
                    t, cd, plan.impurity, plan.task, plan.min_records))(
                tb, cand_p[:, :plan.m_num].T)
        with jax.named_scope("level.merge"):
            best_feat = jnp.argmax(g, axis=0).astype(jnp.int32)
            best_gain = jnp.take_along_axis(g, best_feat[None], 0)[0]
            will_split = sp & jnp.isfinite(best_gain) & (best_gain > 1e-9)
            ks = jnp.cumsum(will_split.astype(jnp.int32))
            new_left = jnp.where(will_split, 2 * ks - 1, 0).astype(jnp.int32)
            new_right = jnp.where(will_split, 2 * ks, 0).astype(jnp.int32)
            feat_of_leaf = jnp.where(will_split, best_feat,
                                     0).astype(jnp.int32)
            thr_sel = jnp.take_along_axis(
                cuts, jnp.clip(best_feat, 0, max(plan.m_num - 1, 0))[None],
                0)[0]
            thr_of_leaf = jnp.where(will_split, thr_sel, 0.0)
        return {"best_feat": best_feat, "best_gain": best_gain,
                "thr": thr_of_leaf, "will_split": will_split,
                "new_left": new_left, "new_right": new_right,
                "feat_of_leaf": feat_of_leaf}

    return jax.vmap(per_tree)(tables, splittable_p, fkeys)
