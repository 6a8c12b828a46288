"""SplitEngine protocol + the local (single-device) engines.

A `SplitEngine` answers ONE question per depth level: "for every open
leaf, what is the best split on my features?" — the paper's supersplit
query.  The level plan (plan.py) owns everything around that answer
(candidate draw, winner argmax, condition eval, reassignment), so an
engine only ever sees per-leaf state and returns per-leaf bests:

    numeric engines:      (gains (m_num, L+1), thresholds (m_num, L+1))
    categorical engines:  (gains (m_cat, L+1), left-masks (m_cat, L+1, V))

Engines are FROZEN, HASHABLE dataclasses: they ride through `jax.jit` as
static arguments of the fused level step, so choosing an engine chooses a
lowering, not a runtime branch.  Local engines are called per tree inside
the plan's tree-axis vmap / lax.map; mesh engines (sharded.py) declare
`batch_native = True` and are instead called ONCE per level with a leading
tree axis, outside the vmap, because `shard_map` composes with an explicit
batch axis far more robustly than with a vmap batching rule.

`LevelInputs` is the full per-tree view of the level state; every engine
reads only the fields its layout needs (the drivers pass zero-size dummies
for the rest, see `SplitEngine.needs_sorted` / `needs_bins`).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import splits


class LevelInputs(NamedTuple):
    """Per-tree level state handed to engines (see tree.py for shapes).

    Batch-native engines receive the same tuple with a leading tree axis T
    on the per-tree fields (`ord_idx`, `leaf_of`, `w`, `stats`, `totals`,
    `row_counts`, `prev_tables`, `parent_of`, `sib_of`, `slot_of`); the
    shared read-only fields (`num`, `cat`, `labels`, `sorted_vals`,
    `sorted_idx`, `bin_of`, `bin_edges`) never batch.

    The last four fields are the histogram-subtraction state (DESIGN.md
    §6), present only when the plan carries tables (`st.subtract`):
    `prev_tables` holds the previous level's merged per-leaf tables
    (indexed by the previous level's leaf ids), and the three per-leaf
    maps relate the CURRENT frontier to it — `parent_of[l]` is l's parent
    leaf id at the previous level, `sib_of[l]` its sibling's current id,
    `slot_of[l]` its packed build slot (0 = table derived by subtraction).
    """
    num: jnp.ndarray           # (m_num, n) raw numeric columns, feature-major
    cat: jnp.ndarray           # (n, m_cat) raw categorical columns
    labels: jnp.ndarray        # (n,) class ids / regression targets
    sorted_vals: jnp.ndarray   # (m_num, n) presorted values (or (0, 0))
    sorted_idx: jnp.ndarray    # (m_num, n) presorted row ids (or (0, 0))
    bin_of: jnp.ndarray        # (m_num, n) packed hist bucket ids (or (0, 0))
    bin_edges: jnp.ndarray     # (m_num, B) hist bucket edges (or (0, 0))
    ord_idx: jnp.ndarray       # (m_num, n) (leaf, value)-sorted order (or (0, 0))
    leaf_of: jnp.ndarray       # (n,) leaf id per row, 0 = closed
    w: jnp.ndarray             # (n,) bag weights
    stats: jnp.ndarray         # (n, S) row stats
    totals: jnp.ndarray        # (L+1, S) per-leaf stat totals
    row_counts: jnp.ndarray    # (L+1,) rows per leaf (leaf-ordered layout)
    prev_tables: jnp.ndarray = None   # (m_num, Wprev, S, B) previous level
    parent_of: jnp.ndarray = None     # (L+1,) parent leaf id at prev level
    sib_of: jnp.ndarray = None        # (L+1,) sibling's current leaf id
    slot_of: jnp.ndarray = None       # (L+1,) packed build slot, 0 = derive


class LevelStatics(NamedTuple):
    """The hashable static config shared by every engine call.

    `carry_tables`/`subtract` are per-DISPATCH statics the plan fills in
    (plan.statics defaults them off): `carry_tables` asks a histogram
    engine to also return its merged tables (the loop state of the
    subtraction recurrence); `subtract` means the inputs carry a valid
    previous level (prev_tables + maps), so only build-slot leaves are
    scattered and siblings derive by parent − sibling.
    """
    m_num: int
    m_cat: int
    max_arity: int
    num_classes: int
    num_bins: int
    impurity: str
    task: str
    min_records: float
    carry_tables: bool = False
    subtract: bool = False


class SplitEngine:
    """Base protocol.  Subclasses are frozen dataclasses (hashable)."""

    kind: str = "numeric"       # "numeric" | "categorical"
    batch_native: bool = False  # True: called once per level with a T axis
    uses_ord: bool = False      # True: wants the incremental leaf order
    needs_sorted: bool = False  # True: wants sorted_vals/sorted_idx
    needs_bins: bool = False    # True: wants bin_of/bin_edges (hist layout)
    bin_cut_thresholds: bool = False  # True: thresholds are BIN INDICES
                                # (host decodes via edges; condition eval
                                # runs on the bin cache, not float columns)
    carries_tables: bool = False  # True: supports the table-carrying
                                # subtraction protocol (st.carry_tables)

    def supersplits(self, inp: LevelInputs, st: LevelStatics, Lp: int,
                    cand: jnp.ndarray):
        """Per-tree supersplit: cand is (m, L+1) bool (leaf 0 = False)."""
        raise NotImplementedError

    def supersplits_batched(self, inp: LevelInputs, st: LevelStatics,
                            Lp: int, cand: jnp.ndarray):
        """Whole-batch supersplit (batch-native engines only): per-tree
        fields of `inp` and `cand` carry a leading tree axis T."""
        raise NotImplementedError

    def row_shards(self) -> int:
        """Row-shard count the driver must keep n divisible by (pruning)."""
        return 1

    def table_updates(self, st: LevelStatics, n: int, subtract: bool) -> int:
        """Scatter updates one tree's table build makes at a level of n
        rows (the `level.table_updates` counter); 0 for an engine that
        scatters no bin tables."""
        return 0

    # -- out-of-core streaming (DESIGN.md §8) -------------------------------
    #
    # A streaming-capable hist engine splits its table build into a
    # chunk recurrence: `stream_init` allocates the per-level accumulator,
    # `stream_accumulate` adds one fixed-shape row chunk (called inside
    # the jitted chunk step, once per chunk), and `stream_finalize` merges
    # the accumulator into the (T, m_num, L+1, S, B) tables the scorer
    # reads (called once per level).  Classification tables are
    # integer-valued f32, so chunked accumulation is bit-equal to the
    # single-pass scatter regardless of chunk boundaries.

    supports_stream: bool = False

    def stream_init(self, T: int, st: LevelStatics, Lp: int):
        """Zero accumulator for one level of T trees."""
        raise NotImplementedError

    def stream_accumulate(self, acc, bins, leaf, w, stats, labels,
                          st: LevelStatics, Lp: int):
        """acc + tables of one chunk: bins (m, c); leaf/w (T, c);
        stats (T, c, S); labels (c,)."""
        raise NotImplementedError

    def stream_finalize(self, acc):
        """Accumulator -> merged (T, m_num, Lp+1, S, B) tables."""
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Shared per-column helpers (also used by the sharded engines)
# ---------------------------------------------------------------------------

@jax.jit
def _gather_sorted_level(sorted_idx, leaf_of, w, stats):
    """Per-column gathers of the level state in presorted order."""
    return leaf_of[sorted_idx], w[sorted_idx], stats[sorted_idx]


def _numeric_supersplits(backend, sorted_vals, sorted_idx, leaf_of, w, stats,
                         cand, Lp, impurity, task, min_records):
    """vmap the chosen exact backend over numerical columns.

    sorted_vals/sorted_idx: (m_num, n); cand: (m_num, Lp+1).
    Returns gains (m_num, Lp+1), thresholds (m_num, Lp+1).
    """
    fn = splits.NUMERIC_BACKENDS[backend]
    def per_col(v, si, cl):
        lf, ww, st = _gather_sorted_level(si, leaf_of, w, stats)
        return fn(v, lf, ww, st, cl, Lp, impurity, task, min_records)
    return jax.vmap(per_col)(sorted_vals, sorted_idx, cand)


def _categorical_supersplits(cat_cols, leaf_of, w, stats, cand, Lp, max_arity,
                             impurity, task, min_records):
    """vmap exact categorical search over columns padded to max_arity."""
    def per_col(x, cl):
        return splits.best_categorical_split(
            x, leaf_of, w, stats, cl, Lp, max_arity, impurity, task, min_records)
    return jax.vmap(per_col)(cat_cols, cand)


# ---------------------------------------------------------------------------
# Local engines
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ExactNumeric(SplitEngine):
    """The paper's midpoint-exhaustive numeric search, all local backends.

    backend = "segment" (default) reads the incrementally-maintained
    (leaf, value)-sorted layout when the driver provides it (DESIGN.md §2)
    and falls back to the presorted counting-sort path otherwise;
    "scan" is the faithful Alg. 1 streaming pass; "kernel" the Pallas
    split_scan path.
    """
    backend: str = "segment"

    needs_sorted = True

    @property
    def uses_ord(self) -> bool:
        return self.backend == "segment"

    def supersplits(self, inp, st, Lp, cand):
        if self.backend == "kernel":
            from repro.kernels import ops as kops
            return kops.split_scan_supersplit(
                inp.sorted_vals, inp.sorted_idx, inp.leaf_of, inp.w,
                inp.labels, cand, Lp, st.impurity, st.task, st.min_records,
                num_classes=st.num_classes)
        if inp.ord_idx.size:
            # leaf-ordered fast path: no per-level counting sort.  Shared
            # per-leaf totals are exact for classification (integer bag
            # counts); regression reduces per column to keep the reference
            # builder's float summation order bit-for-bit.
            tot = inp.totals if st.task == "classification" else None
            lf_pos = inp.leaf_of[inp.ord_idx[0]]    # same for every column
            inbag = (inp.w > 0)[inp.ord_idx] & (lf_pos > 0)[None]
            ord_vals = jnp.take_along_axis(inp.num, inp.ord_idx, axis=1)
            ord_stats = splits.stat_planes(
                inp.labels[inp.ord_idx], inp.w[inp.ord_idx],
                inp.stats.shape[-1], st.task)                # (S, m, n)
            return splits.best_numeric_split_leaf_ordered(
                ord_vals, lf_pos, inbag, ord_stats, cand, Lp,
                st.impurity, st.task, st.min_records, totals=tot,
                row_counts=inp.row_counts)
        return _numeric_supersplits(
            self.backend, inp.sorted_vals, inp.sorted_idx, inp.leaf_of,
            inp.w, inp.stats, cand, Lp, st.impurity, st.task, st.min_records)


# ---------------------------------------------------------------------------
# Histogram-mode table building (shared by HistNumeric and the mesh engine)
# ---------------------------------------------------------------------------

def _hist_build_rows(inp, subtract, compact):
    """The (bin_of, scatter slots, w, stats, labels) a table build reads.

    Plain mode scatters every row under its raw leaf id.  Subtraction mode
    remaps rows through `slot_of` — rows of derive-slot leaves land in the
    discarded slot 0 — and, when `compact` (single-device only: the bound
    below is global, not per row shard), GATHERS the build rows into an
    n//2 buffer first, so the scatter touches at most half the rows: build
    leaves are the smaller child of every split, so their row total is
    ≤ floor(n/2).  Compaction keeps row order (nonzero is stable), so the
    per-slot accumulation order — and hence the tables — match the
    uncompacted scatter exactly.
    """
    if not subtract:
        return inp.bin_of, inp.leaf_of, inp.w, inp.stats, inp.labels
    slot_row = inp.slot_of[inp.leaf_of]                   # (n,) build slots
    if not compact:
        return inp.bin_of, slot_row, inp.w, inp.stats, inp.labels
    n = inp.leaf_of.shape[0]
    n2 = max(n // 2, 1)
    idx = jnp.nonzero(slot_row > 0, size=n2, fill_value=n)[0]
    valid = idx < n
    idxc = jnp.minimum(idx, n - 1)
    return (inp.bin_of[:, idxc],
            jnp.where(valid, slot_row[idxc], 0),
            jnp.where(valid, inp.w[idxc], 0.0),
            inp.stats[idxc], inp.labels[idxc])


def _class_ids(labels, st):
    """The class ids `feature_count_tables` scatters at (one update per
    row and column), or None for regression's plane path."""
    return labels if st.task == "classification" else None


def _scatter_updates(st, rows):
    """Updates one tree's `feature_count_tables` call makes over `rows`
    rows: one per column on the class path, S per column on the plane
    path (regression, S = 3)."""
    return st.m_num * rows * (1 if st.task == "classification" else 3)


def _expand_subtracted(packed, prev_tables, parent_of, sib_of, slot_of):
    """Full-width tables from packed build tables + the parent recurrence.

    packed: (m, Wb, S, B) merged build-slot tables; returns (m, L+1, S, B)
    where build leaves gather their packed slot and every derive leaf is
    `parent − sibling` — exact for classification (integer-valued counts),
    which is why the plan only enables subtraction there.
    """
    from_build = packed[:, slot_of]                       # (m, L+1, S, B)
    sib = packed[:, slot_of[sib_of]]
    derived = prev_tables[:, parent_of] - sib
    return jnp.where((slot_of > 0)[None, :, None, None], from_build, derived)


@dataclasses.dataclass(frozen=True)
class HistNumeric(SplitEngine):
    """PLANET-style histogram numeric search (DESIGN.md §6).

    Reads ONLY the bit-packed bin cache (`bin_of`, uint8/uint16): per-leaf
    (bin × stat) tables for all columns are built in one pass — the Pallas
    `feat_hist` kernel under backend="kernel", a single flat scatter
    (`splits.feature_count_tables`) otherwise — and
    `splits.best_numeric_split_histogram` scores the bucket boundaries,
    returning BIN INDICES the host decodes against the (host-side) float
    edges.  Under `st.subtract` only the smaller child of each split is
    scattered (rows compacted to an n//2 buffer) and its sibling derives
    by parent − sibling from the carried previous-level tables.
    """
    backend: str = "segment"

    needs_bins = True
    bin_cut_thresholds = True
    carries_tables = True
    supports_stream = True

    def stream_init(self, T, st, Lp):
        S = st.num_classes if st.task == "classification" else 3
        return jnp.zeros((T, st.m_num, Lp + 1, S, st.num_bins), jnp.float32)

    def stream_accumulate(self, acc, bins, leaf, w, stats, labels, st, Lp):
        if self.backend == "kernel":
            return acc + jax.vmap(
                lambda lf, ww, stt: self._tables(None, st, Lp + 1, bins, lf,
                                                 ww, stt, labels))(
                leaf, w, stats)
        # the tree axis folds into the one flat scatter (no vmap)
        return acc + splits.feature_count_tables(
            bins, leaf, w, stats, Lp, st.num_bins,
            labels=_class_ids(labels, st))

    def stream_finalize(self, acc):
        return acc

    def _tables(self, inp, st, W, bins, slots, w, stats, labels):
        if self.backend == "kernel":
            from repro.kernels import ops as kops
            return kops.feature_tables(
                bins, slots, w, labels, B=st.num_bins, W=W, task=st.task,
                num_classes=st.num_classes)
        return splits.feature_count_tables(bins, slots, w, stats, W - 1,
                                           st.num_bins,
                                           labels=_class_ids(labels, st))

    def table_updates(self, st, n, subtract):
        if self.backend == "kernel":
            return 0
        return _scatter_updates(st, max(n // 2, 1) if subtract else n)

    def supersplits(self, inp, st, Lp, cand):
        Wb = Lp // 2 + 1 if st.subtract else Lp + 1
        with jax.named_scope("level.supersplit.tables"):
            bins, slots, w, stats, labels = _hist_build_rows(
                inp, st.subtract, compact=True)
            packed = self._tables(inp, st, Wb, bins, slots, w, stats,
                                  labels)
            if st.subtract:
                tables = _expand_subtracted(packed, inp.prev_tables,
                                            inp.parent_of, inp.sib_of,
                                            inp.slot_of)
            else:
                tables = packed
        with jax.named_scope("level.supersplit.score"):
            g, c = jax.vmap(
                lambda tb, cd: splits.best_numeric_split_histogram(
                    tb, cd, st.impurity, st.task, st.min_records))(
                tables, cand)
        if st.carry_tables:
            return g, c, tables
        return g, c


@dataclasses.dataclass(frozen=True)
class CategoricalTable(SplitEngine):
    """Exact categorical search from (leaf × category × stat) count tables
    + Breiman ordering; backend="kernel" builds the tables with the Pallas
    table kernel (`kernels/feat_hist.py`)."""
    backend: str = "segment"

    kind = "categorical"

    def supersplits(self, inp, st, Lp, cand):
        if self.backend == "kernel":
            from repro.kernels import ops as kops
            tables = kops.categorical_tables(
                inp.cat.T, inp.leaf_of, inp.w, inp.labels, V=st.max_arity,
                Lp=Lp, task=st.task, num_classes=st.num_classes)
            return jax.vmap(
                lambda tb, c: splits.best_categorical_split_from_table(
                    tb, c, st.impurity, st.task, st.min_records))(
                tables, cand)
        return _categorical_supersplits(
            inp.cat.T, inp.leaf_of, inp.w, inp.stats, cand, Lp,
            st.max_arity, st.impurity, st.task, st.min_records)


@dataclasses.dataclass(frozen=True, eq=False)   # identity hash: one trace
class LegacyFn(SplitEngine):                    # per closure, as before
    """Adapter for a bare `supersplit_fn` closure (the pre-SplitEngine
    API).  Per-tree only: `RandomForest.fit` warns and routes these to the
    per-tree builder, because an arbitrary closure composes with neither
    the tree-axis vmap nor the batch-native protocol."""
    fn: Callable
    hist: bool = False          # hist-mode signature (bin_of, bin_edges, ...)

    @property
    def needs_sorted(self) -> bool:     # type: ignore[override]
        return not self.hist

    @property
    def needs_bins(self) -> bool:       # type: ignore[override]
        return self.hist

    def supersplits(self, inp, st, Lp, cand):
        if self.hist:
            return self.fn(inp.bin_of, inp.bin_edges, inp.leaf_of, inp.w,
                           inp.stats, cand, Lp, st.impurity, st.task,
                           st.min_records)
        return self.fn(inp.sorted_vals, inp.sorted_idx, inp.leaf_of, inp.w,
                       inp.stats, cand, Lp, st.impurity, st.task,
                       st.min_records)
