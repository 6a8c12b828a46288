"""Level-by-level decision tree builders (paper Alg. 2) + flat tree arrays.

The *tree builder* is the control plane (host Python, like the paper's tree
builder workers which "do not have access to the dataset"); the per-level
supersplit search and condition evaluation are the data plane (jitted JAX,
the paper's splitters).  All nodes of a depth are split together, so the
whole dataset is scanned once per candidate feature per LEVEL — never per
node — which is the paper's central complexity win over Sprint.

This module is the HOST DRIVER layer only.  The data plane lives in
`repro.core.level`: a `LevelPlan` composes a numeric and a categorical
`SplitEngine` (exact / histogram × local / mesh-sharded) into ONE fused
jitted program per depth level (DESIGN.md §7).  The drivers here own the
flat-tree bookkeeping (`_NodeAccum`), the frontier padding, the Sprint
pruning switch, and the per-level host protocol:

  * `build_tree` — one tree, one fused program per depth
    (`level.plan._fused_level_step`); the fallback for legacy
    `supersplit_fn` closures, otherwise prefer `build_forest`.
  * `build_forest` — a whole BATCH of trees per level program (vmap /
    lax.map over a leading tree axis, T·D → D dispatches, DESIGN.md §3),
    bit-identical per tree.  The host loop is PIPELINED: each level's
    Python bookkeeping (`_grow_level`, node values) is deferred until
    after the NEXT level's program has been dispatched, so host work
    overlaps device compute; transfers start with `copy_to_host_async`.
  * `build_tree_reference` (repro.core.reference) — the pre-fusion seed
    builder, kept as the executable specification the fused builders must
    reproduce bit-for-bit.

Per-level network/disk accounting (paper Table 1) is recorded in
`LevelStats` by the builders: one bit per sample per level broadcast
("Dn bits in D allreduce"), the ⌈log2(ℓ+1)⌉·n class-list bits, and the
number of sequential passes over the data.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import bagging, class_list, presort, pruning, splits
from repro.core.level.engines import LegacyFn, SplitEngine
from repro.core.level.plan import (_BATCH_VMAP_ELEMS_DEFAULT,
                                   _fused_level_step,
                                   _fused_level_step_batched, _leaf_totals,
                                   _pad_leaves, make_plan)

# Tuning knob read (late-bound) by level.plan: above this many row-state
# elements (T·m_num·n) the batched level step switches from vmap to
# lax.map over trees — see `level.plan._fused_level_step_batched`.
_BATCH_VMAP_ELEMS = _BATCH_VMAP_ELEMS_DEFAULT


# ---------------------------------------------------------------------------
# Hyper-parameters & flat tree
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TreeParams:
    max_depth: int = 20
    min_records: float = 1.0        # paper: "minimum number of records in a leaf"
    num_candidates: Optional[int] = None  # m' (None = ceil(sqrt(m)), the paper default)
    impurity: str = "gini"          # gini | entropy | variance
    task: str = "classification"
    backend: str = "segment"        # segment | scan | kernel (Pallas)
    # exact = the paper's midpoint-exhaustive search (default); hist = the
    # PLANET-style contrast baseline: numeric columns quantized once into
    # <= num_bins buckets, splits scored on bucket boundaries only, from
    # per-leaf (bin × class) count tables (DESIGN.md §6)
    split_mode: str = "exact"       # exact | hist
    num_bins: int = 255             # histogram-mode bucket budget per column
    # histogram subtraction (DESIGN.md §6): carry each level's per-leaf
    # tables and build only the SMALLER child of every split, deriving the
    # sibling as parent − sibling — ~half the table-build work per level
    # and, sharded, ~half the psum payload.  Classification only (integer
    # tables make the subtraction exact; regression always rebuilds
    # plain); results are bit-identical either way, so this is purely a
    # perf knob.
    hist_subtract: bool = True
    usb: bool = False               # unique set of bagged features per depth (§3.2)
    bagging: str = "poisson"        # poisson | multinomial | none
    leaf_pad: int = 8               # pad open-leaf count to multiples (recompile bound)
    # Sprint-style record pruning (paper §3): when the fraction of samples
    # sitting in CLOSED leaves reaches this threshold, compact the dataset
    # (drop those rows, filter the presorted order — no re-sort needed).
    # 1.0 disables it, which is the paper's Leo configuration ("this
    # operation is not triggered during the experimentation").
    prune_closed_frac: float = 1.0


@dataclasses.dataclass
class Tree:
    """Flat-array decision tree (numpy, host-side)."""
    feature: np.ndarray        # (N,) int32; -1 = leaf
    threshold: np.ndarray      # (N,) float32 (numeric nodes)
    is_cat: np.ndarray         # (N,) bool
    cat_mask: np.ndarray       # (N, max_arity) bool; True -> go LEFT
    children: np.ndarray       # (N, 2) int32 [left, right]
    value: np.ndarray          # (N, C) class distribution / (N, 1) mean
    n_node: np.ndarray         # (N,) in-bag weight reaching the node
    gain: np.ndarray           # (N,) split gain (0 for leaves)
    depth: np.ndarray          # (N,) int32
    m_num: int
    task: str

    @property
    def num_nodes(self) -> int:
        return len(self.feature)

    @property
    def num_leaves(self) -> int:
        return int((self.feature < 0).sum())

    @property
    def max_depth_reached(self) -> int:
        return int(self.depth.max()) if self.num_nodes else 0

    def node_density(self) -> float:
        """Paper §5: #leaves / 2^D for the deepest depth D."""
        d = self.max_depth_reached
        return self.num_leaves / float(2 ** d) if d else 1.0

    def sample_density(self) -> float:
        """Paper §5: fraction of in-bag weight reaching depth-D leaves."""
        d = self.max_depth_reached
        leaves = self.feature < 0
        bottom = leaves & (self.depth == d)
        tot = self.n_node[leaves].sum()
        return float(self.n_node[bottom].sum() / tot) if tot > 0 else 0.0

    def predict_raw(self, num: jnp.ndarray, cat: jnp.ndarray) -> jnp.ndarray:
        """(B, C) distributions / (B, 1) means."""
        return _predict_jit(
            jnp.asarray(self.feature), jnp.asarray(self.threshold),
            jnp.asarray(self.is_cat), jnp.asarray(self.cat_mask),
            jnp.asarray(self.children), jnp.asarray(self.value),
            num, cat, self.m_num, int(self.depth.max()) + 1)


@dataclasses.dataclass
class LevelStats:
    """Per-level complexity counters (benchmarks/table1)."""
    depth: int
    open_leaves: int
    network_bits_bitmap: int     # the 1-bit-per-sample broadcast
    network_bits_supersplit: int # partial supersplit payloads (tiny)
    class_list_bits: int         # n * ceil(log2(l+1))
    feature_passes: int          # sequential passes over candidate columns
    rows_scanned: int
    # hist mode: bytes of the per-level merged table payload — exactly
    # what ShardedHistNumeric psums (m·width·B·S f32); under subtraction
    # only the packed build slots (width Lp//2+1 vs Lp+1) cross the
    # network, which is the ~2x payload cut benchmarks/run.py hist records
    hist_table_bytes: int = 0


# ---------------------------------------------------------------------------
# Setup helpers shared by the drivers
# ---------------------------------------------------------------------------

def _tree_setup(sorted_vals, arities, labels, params):
    if params.split_mode not in ("exact", "hist"):
        raise ValueError(f"unknown split_mode {params.split_mode!r} "
                         "(expected 'exact' or 'hist')")
    if params.split_mode == "hist" and params.num_bins < 2:
        raise ValueError("hist mode needs num_bins >= 2")
    n = int(labels.shape[0])
    m_num = int(sorted_vals.shape[0]) if sorted_vals.size else 0
    m_cat = len(arities)
    m = m_num + m_cat
    max_arity = max(arities) if arities else 1
    m_prime = params.num_candidates or max(
        1, math.isqrt(m) + (0 if math.isqrt(m) ** 2 == m else 1))
    return n, m_num, m_cat, m, max_arity, m_prime


def _hist_state(num, sorted_vals, params, m_num, bin_of, bin_edges):
    """Resolve the hist-mode bucket state (zero-size dummies in exact mode).

    When the caller (RandomForest/GBTModel.fit) did not precompute the
    quantization, derive it here from the presorted values — once per tree
    build, shared by every level.  Pre-quantized state is VALIDATED
    against `params`: a bin-count or shape disagreement used to be
    silently ignored (the engines read whatever bucket ids they were
    handed) and now raises at fit time.
    """
    if params.split_mode == "hist" and m_num:
        if bin_of is None:
            bin_of, bin_edges = presort.quantize(num, sorted_vals,
                                                 params.num_bins)
        if bin_edges is None:
            raise ValueError("pre-quantized bin_of needs its bin_edges")
        if tuple(bin_edges.shape) != (m_num, params.num_bins):
            raise ValueError(
                f"pre-quantized bucket state disagrees with TreeParams: "
                f"bin_edges shape {tuple(bin_edges.shape)} but the fit has "
                f"m_num={m_num} numeric columns and num_bins="
                f"{params.num_bins} — re-quantize the dataset (e.g. "
                f"TabularDataset.quantize(num_bins={params.num_bins})) or "
                f"set TreeParams(num_bins={bin_edges.shape[-1]})")
        if (tuple(bin_of.shape)[0] != m_num
                or bin_of.shape[-1] != num.shape[0]):
            raise ValueError(
                f"pre-quantized bin_of shape {tuple(bin_of.shape)} does "
                f"not match the dataset ((m_num, n) = "
                f"({m_num}, {num.shape[0]}))")
        if not jnp.issubdtype(bin_of.dtype, jnp.integer):
            raise ValueError(f"bin_of must be integer bucket ids, got "
                             f"dtype {bin_of.dtype}")
        if np.iinfo(np.dtype(bin_of.dtype)).max < params.num_bins - 1:
            raise ValueError(
                f"bin_of dtype {bin_of.dtype} cannot hold num_bins="
                f"{params.num_bins} bucket ids (expected "
                f"{np.dtype(presort.bin_dtype(params.num_bins)).name})")
        return bin_of, bin_edges
    return jnp.zeros((0, 0), presort.bin_dtype(params.num_bins)), \
        jnp.zeros((0, 0), jnp.float32)


def _resolve_engines(params, supersplit_fn, engine, cat_engine):
    """Back-compat: a bare `supersplit_fn` closure wraps into a LegacyFn
    engine; a SplitEngine passed as `supersplit_fn` IS the engine."""
    if supersplit_fn is not None:
        if engine is not None:
            raise ValueError(
                "pass either engine= (a SplitEngine) or supersplit_fn=, "
                "not both — one of them would be silently ignored")
        if isinstance(supersplit_fn, SplitEngine):
            engine = supersplit_fn
        else:
            engine = LegacyFn(fn=supersplit_fn,
                              hist=params.split_mode == "hist")
    return engine, cat_engine


def _make_plan(params, *, sorted_vals, arities, labels, num_classes,
               supersplit_fn=None, engine=None, cat_engine=None):
    n, m_num, m_cat, m, max_arity, m_prime = _tree_setup(
        sorted_vals, arities, labels, params)
    engine, cat_engine = _resolve_engines(params, supersplit_fn, engine,
                                          cat_engine)
    plan = make_plan(params, m_num=m_num, m_cat=m_cat, max_arity=max_arity,
                     num_classes=num_classes, m_prime=m_prime,
                     engine=engine, cat_engine=cat_engine)
    return plan, (n, m_num, m_cat, m, max_arity, m_prime)


def _zeros_unless(cond, arr, dtype):
    return arr if cond else jnp.zeros((0, 0), dtype)


def _start_fetch(tree) -> None:
    """Start the non-blocking D2H transfer of every device array."""
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "copy_to_host_async"):
            leaf.copy_to_host_async()


def _nbytes(tree) -> int:
    """Bytes of the (host) arrays in a pytree: what a fetch brought back."""
    return sum(np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(tree))


def _level_num(num):
    """The numeric columns as the level programs read them: feature-major
    (m_num, n), materialized once per fit.  A TPU tiles an array's minor
    axis in 128 lanes, so the row-major (n, m_num) layout pads a few
    columns to 128 and lets the compiler lay the programs' (m_num, n)
    intermediates out the same padded way."""
    return jnp.asarray(num).T


# ---------------------------------------------------------------------------
# Host-side flat-tree bookkeeping (Alg. 2 step 8)
# ---------------------------------------------------------------------------

class _NodeAccum:
    """Host-side flat-tree accumulator (Alg. 2 step 8 bookkeeping).

    One per tree; the builders append nodes level by level and
    `_assemble_tree` freezes the lists into the numpy `Tree` arrays.
    """

    def __init__(self, num_classes: int, task: str):
        self.feature: list = []
        self.threshold: list = []
        self.is_cat: list = []
        self.cat_mask: list = []
        self.children: list = []
        self.value: list = []
        self.n_node: list = []
        self.gain: list = []
        self.depth: list = []
        self._C = max(num_classes, 2) if task == "classification" else 1

    def new_node(self, depth: int) -> int:
        self.feature.append(-1)
        self.threshold.append(0.0)
        self.is_cat.append(False)
        self.cat_mask.append(None)
        self.children.append([-1, -1])
        self.value.append(np.zeros(self._C, np.float32))
        self.n_node.append(0.0)
        self.gain.append(0.0)
        self.depth.append(depth)
        return len(self.feature) - 1

    def set_value(self, node: int, totals_row: np.ndarray, count: float,
                  task: str) -> None:
        """Node value from its leaf-totals row (distribution / mean)."""
        self.n_node[node] = float(count)
        if task == "classification":
            tot = max(count, 1e-12)
            self.value[node] = (totals_row / tot).astype(np.float32)
        else:
            wsum = max(totals_row[0], 1e-12)
            self.value[node] = np.array([totals_row[1] / wsum], np.float32)


def _grow_level(acc: _NodeAccum, open_nodes: list, host: dict, L: int,
                m_num: int, depth: int, edges_np=None) -> tuple[list, bool]:
    """Alg. 2 step 8 for ONE tree: grow the flat tree from a level struct.

    `host` holds the fetched per-leaf arrays of one tree (best_feat /
    best_gain / thr / mask / will_split, each (Lp+1,)-indexed by leaf id).
    Shared by `build_tree` and `build_forest` so their bookkeeping cannot
    drift.  Returns (next level's open node ids, whether any leaf split).

    `edges_np` ((m_num, B) numpy) is the hist fast path's HOST-side
    threshold decode table: the level program reports the winning BIN
    INDEX (the float edges never ride to device, DESIGN.md §6), and the
    recorded node threshold is `edges[col, cut]` — the same float the old
    device-side decode produced, so trees are unchanged.
    """
    bf, bg = host["best_feat"], host["best_gain"]
    thr, mask, ws = host["thr"], host["mask"], host["will_split"]
    next_open: list[int] = []
    any_split = False
    for h in range(1, L + 1):
        if not ws[h]:
            continue
        node = open_nodes[h - 1]
        j = int(bf[h])
        any_split = True
        acc.feature[node] = j
        acc.gain[node] = float(bg[h])
        if j < m_num:
            if edges_np is not None:
                acc.threshold[node] = float(edges_np[j, int(thr[h])])
            else:
                acc.threshold[node] = float(thr[h])
        else:
            acc.is_cat[node] = True
            acc.cat_mask[node] = mask[h].copy()
        lc, rc = acc.new_node(depth + 1), acc.new_node(depth + 1)
        acc.children[node] = [lc, rc]
        next_open.extend([lc, rc])
    return next_open, any_split


def _child_maps(ws, kc, L, Lp_next):
    """The next level's subtraction maps from this level's split bitmap.

    ws (Lp+1,) bool: which leaves split; kc (2·Lp+1,) int: row counts of
    the new child leaves (the level struct's key_counts).  Returns
    (parent_of, sib_of, slot_of), each (Lp_next+1,) int32 indexed by the
    NEW leaf ids: parent/sibling per child, and the packed build slot —
    assigned to the SMALLER child of each split (ties: left), 0 for the
    derive sibling.  Build slots stay <= Lp_next // 2, the packed table
    width the engines scatter into (build rows are therefore <= n // 2,
    the compaction bound in level/engines.py).
    """
    parent = np.zeros(Lp_next + 1, np.int32)
    sib = np.zeros(Lp_next + 1, np.int32)
    slot = np.zeros(Lp_next + 1, np.int32)
    k = 0
    for h in range(1, L + 1):
        if not ws[h]:
            continue
        k += 1
        lc, rc = 2 * k - 1, 2 * k
        parent[lc] = parent[rc] = h
        sib[lc], sib[rc] = rc, lc
        slot[lc if kc[lc] <= kc[rc] else rc] = k
    return parent, sib, slot


def _assemble_tree(acc: _NodeAccum, max_arity, m_num, task) -> Tree:
    N = len(acc.feature)
    cat_mask_arr = np.zeros((N, max_arity), bool)
    for i, cm in enumerate(acc.cat_mask):
        if cm is not None:
            cat_mask_arr[i, :len(cm)] = cm
    return Tree(
        feature=np.asarray(acc.feature, np.int32),
        threshold=np.asarray(acc.threshold, np.float32),
        is_cat=np.asarray(acc.is_cat, bool),
        cat_mask=cat_mask_arr,
        children=np.asarray(acc.children, np.int32),
        value=np.stack(acc.value).astype(np.float32),
        n_node=np.asarray(acc.n_node, np.float32),
        gain=np.asarray(acc.gain, np.float32),
        depth=np.asarray(acc.depth, np.int32),
        m_num=m_num, task=task)


# ---------------------------------------------------------------------------
# The per-tree driver (Alg. 2)
# ---------------------------------------------------------------------------

def build_tree(
    *,
    num: jnp.ndarray, cat: jnp.ndarray, labels: jnp.ndarray,
    sorted_vals: jnp.ndarray, sorted_idx: jnp.ndarray,
    arities: tuple[int, ...], num_classes: int,
    params: TreeParams, seed: int, tree_idx: int,
    collect_stats: bool = False,
    supersplit_fn=None,
    bin_of: Optional[jnp.ndarray] = None,
    bin_edges: Optional[jnp.ndarray] = None,
    engine: Optional[SplitEngine] = None,
    cat_engine: Optional[SplitEngine] = None,
) -> tuple[Tree, list[LevelStats]]:
    """Train ONE tree with one fused jitted device program per depth level.

    Args (shapes):
      num / cat:     (n, m_num) float32 / (n, m_cat) int32 raw columns.
      labels:        (n,) int32 class ids (classification) or float32
                     targets (regression).
      sorted_vals / sorted_idx: (m_num, n) per-column presorted values and
                     row indices (presort.presort_columns) — computed once
                     per forest and shared by every tree.
      arities:       per categorical column arity; categories are
                     0..arity-1, padded to max(arities) inside the step.
      num_classes:   stat width C for classification (S = C); regression
                     uses S = 3 ([w, wy, wy²]) regardless.
      params:        TreeParams; `params.backend` picks the numeric
                     supersplit engine — "segment" (default; incrementally
                     maintained (leaf, value)-sorted layout, no per-level
                     sort), "scan" (faithful Alg. 1 sequential pass) or
                     "kernel" (Pallas split_scan/feat_hist; interpret mode
                     off-TPU).
      seed/tree_idx: seeded bagging + candidate draws (paper §2.2) — all
                     randomness is a pure function of these two.
      engine/cat_engine: explicit `level.SplitEngine` overrides (e.g. the
                     mesh engines of `level.sharded`); default resolves
                     the local engine for `params.split_mode`/`backend`.
      supersplit_fn: back-compat — a SplitEngine here is used as `engine`;
                     a bare closure (the pre-engine API) wraps into
                     `level.LegacyFn` and runs per-tree, unbatched.
      bin_of/bin_edges: hist-mode bucket state ((m_num, n) int32 bucket ids
                     and (m_num, num_bins) f32 upper edges) as produced by
                     `TabularDataset.quantize`; derived here from
                     `sorted_vals` when omitted.  Ignored in exact mode.

    Produces exactly the trees of `build_tree_reference` (asserted by
    tests/test_fused_level.py) while the host does bookkeeping only: per
    level it uploads the tiny (splittable, totals) pair and fetches one
    small per-leaf struct; all row-indexed state stays on device.  To train
    many trees, prefer `build_forest`, which runs this same level plan over
    a whole tree batch.

    Returns (Tree, [LevelStats]) — the flat host-side tree and, when
    `collect_stats`, the per-level paper-Table-1 counters.
    """
    plan, (n, m_num, m_cat, m, max_arity, m_prime) = _make_plan(
        params, sorted_vals=sorted_vals, arities=arities, labels=labels,
        num_classes=num_classes, supersplit_fn=supersplit_fn, engine=engine,
        cat_engine=cat_engine)
    task = params.task
    hist = params.split_mode == "hist"
    # dataset.from_numpy keeps columns HOST-side (lazy for mmap inputs);
    # device-put once here so every level reads device arrays, not
    # re-uploaded numpy (no-op when already on device)
    num, cat, labels = jnp.asarray(num), jnp.asarray(cat), jnp.asarray(labels)
    sorted_vals = jnp.asarray(sorted_vals)
    sorted_idx = jnp.asarray(sorted_idx)
    bin_of, bin_edges = _hist_state(num, sorted_vals, params, m_num,
                                    bin_of, bin_edges)
    num = _level_num(num)
    # hist fast path: float edges stay HOST-side, decoding the reported
    # bin cuts into node thresholds (the level program reads only the
    # bit-packed bin cache); `carries` = the subtraction recurrence is on
    carries = plan.carries_tables
    edges_np = np.asarray(bin_edges) if plan.use_bin_cuts else None

    w = bagging.bag_counts(seed, tree_idx, n, params.bagging)
    stats = splits.row_stats(labels, w, num_classes, task)
    fkey = jax.random.fold_in(jax.random.PRNGKey(seed ^ 0x5EED), tree_idx)

    def cnt_np(t):
        return t.sum(-1) if task == "classification" else t[..., 0]

    acc = _NodeAccum(num_classes, task)
    root = acc.new_node(0)
    open_nodes = [root]                       # leaf id h (1-based) -> node id
    leaf_of = jnp.ones((n,), jnp.int32)       # all samples at the root
    stats_log: list[LevelStats] = []

    # the segment engine's leaf-ordered state; other engines read the
    # plain presorted layout (or the bucket state) and get zero-size
    # dummies for the layouts they don't use
    use_ord = plan.use_ord
    # root: all rows in leaf 1, so value order == (leaf, value) order
    ord_idx = sorted_idx if use_ord else jnp.zeros((0, 0), jnp.int32)

    tables = None                   # carried per-leaf hist tables (device)
    maps_src = None                 # (will_split, key_counts, L) of level-1
    no_tables = jnp.zeros((0, 0, 0, 0), jnp.float32)
    no_map = jnp.zeros((0,), jnp.int32)
    totals_np = None
    row_counts_np = None
    batch = f"{tree_idx}:{tree_idx + 1}"     # the tree, as a span arg
    for depth in range(params.max_depth + 1):
        L = len(open_nodes)
        if L == 0:
            break
        Lp = _pad_leaves(L, params.leaf_pad)

        with obs.span("repro.level.prep", depth=depth, batch=batch):
            # leaf totals -> node values & forced closes (carried over from
            # the previous level's fused step; computed once at the root)
            if totals_np is None:
                with obs.span("repro.level.fetch", depth=depth, batch=batch):
                    # waits for the presort / quantize programs too
                    totals_np = np.asarray(_leaf_totals(leaf_of, stats, w,
                                                        Lp))
                row_counts_np = np.zeros(Lp + 1, np.int32)
                row_counts_np[1] = n
            else:
                cur = np.zeros((Lp + 1, totals_np.shape[1]), np.float32)
                cur[:L + 1] = totals_np[:L + 1]
                totals_np = cur
                cur_rc = np.zeros(Lp + 1, np.int32)
                k = min(L + 1, len(row_counts_np))  # threaded if use_ord
                cur_rc[:k] = row_counts_np[:k]
                row_counts_np = cur_rc
            counts = cnt_np(totals_np)
            for h, node in enumerate(open_nodes, start=1):
                acc.set_value(node, totals_np[h], counts[h], task)

            at_max_depth = depth >= params.max_depth
            splittable = np.array(
                [counts[h] >= 2 * params.min_records and not at_max_depth
                 for h in range(1, L + 1)] + [False] * (Lp - L))
            if not splittable.any():
                break
            splittable_p = np.concatenate([[False], splittable])

            # histogram subtraction: relate this frontier to the carried
            # previous-level tables (maps live on the host — tiny per-leaf
            # int arrays — and ride up with the other level inputs)
            subtract = bool(carries and tables is not None
                            and maps_src is not None)
            if subtract:
                parent_np, sib_np, slot_np = _child_maps(*maps_src, Lp)
                maps_dev = (tables, jnp.asarray(parent_np),
                            jnp.asarray(sib_np), jnp.asarray(slot_np))
            else:
                maps_dev = (no_tables, no_map, no_map, no_map)

        # the whole level on device: one dispatch, one small struct back
        obs.count("level.tree_dispatches")
        obs.count("level.tree_rows", n)
        obs.count("level.table_updates", plan.table_updates(n, subtract))
        with obs.span("repro.level.dispatch", depth=depth, batch=batch):
            struct, leaf_of, ord_idx, next_totals, new_tables = \
                _fused_level_step(
                    _zeros_unless(plan.pass_num or not hist, num,
                                  jnp.float32),
                    cat, labels,
                    _zeros_unless(plan.pass_sorted, sorted_vals,
                                  jnp.float32),
                    _zeros_unless(plan.pass_sorted, sorted_idx, jnp.int32),
                    bin_of,
                    _zeros_unless(plan.pass_edges or not hist, bin_edges,
                                  jnp.float32),
                    ord_idx, leaf_of, w, stats,
                    jnp.asarray(splittable_p), jnp.asarray(totals_np),
                    jnp.asarray(row_counts_np), *maps_dev, fkey,
                    jnp.int32(depth), plan=plan, Lp=Lp,
                    need_partition=use_ord and depth + 1 < params.max_depth,
                    subtract=subtract)
            if carries:
                tables = new_tables
            _start_fetch((struct, next_totals))
        with obs.span("repro.level.fetch", depth=depth, batch=batch):
            host, totals_np = jax.device_get((struct, next_totals))
        obs.count("level.fetch_bytes", _nbytes((host, totals_np)))

        with obs.span("repro.level.book", depth=depth, batch=batch):
            if use_ord or carries:
                row_counts_np = host["key_counts"]
            if carries:
                maps_src = (host["will_split"], host["key_counts"], L)

            # Alg. 2 step 8: the host bookkeeping — grow the flat tree
            next_open, any_split = _grow_level(acc, open_nodes, host, L,
                                               m_num, depth,
                                               edges_np=edges_np)

            if collect_stats:
                open_w = float(counts[1:L + 1].sum())
                tbl_w = (Lp // 2 + 1) if subtract else (Lp + 1)
                passes = int(min(m_prime * (1 if params.usb else L), m))
                stats_log.append(LevelStats(
                    depth=depth, open_leaves=L,
                    network_bits_bitmap=int(open_w),
                    network_bits_supersplit=int(m * (Lp + 1) * 64),
                    class_list_bits=class_list.storage_bits(n, L),
                    feature_passes=passes, rows_scanned=n * passes,
                    hist_table_bytes=(m_num * tbl_w * params.num_bins
                                      * int(stats.shape[-1]) * 4 if hist
                                      else 0)))

        if not any_split:
            break
        open_nodes = next_open

        # Sprint-style pruning switch (paper §3): compact rows in closed
        # leaves once they dominate (core/pruning.py).  Device-resident:
        # no host pass, no per-column numpy loop; under the leaf-ordered
        # layout the closed count is already on the host (row_counts[0]
        # from the level struct), so the trigger costs zero transfers.
        if params.prune_closed_frac < 1.0 and n > 0:
            # the ord layout is only current when this level partitioned it
            # (the last level before max_depth skips the partition; the loop
            # terminates right after, so skipping the prune there is free)
            order_current = not use_ord or (depth + 1 < params.max_depth)
            closed = (int(row_counts_np[0]) if use_ord or carries
                      else int(jnp.sum(leaf_of == 0)))
            drop = pruning.plan_drop(n, closed, plan.row_shards,
                                     params.prune_closed_frac)
            if drop and order_current:
                with obs.span("repro.level.prep", depth=depth,
                              batch=batch):
                    (n, leaf_of, ord_idx, sorted_vals, sorted_idx, bin_of,
                     num, cat, stats, w, labels) = pruning.compact_rows(
                        keep=pruning.keep_mask(leaf_of == 0, drop),
                        drop=drop, leaf_of=leaf_of, ord_idx=ord_idx,
                        sorted_vals=sorted_vals, sorted_idx=sorted_idx,
                        bin_of=bin_of, num=num, cat=cat, stats=stats, w=w,
                        labels=labels, use_ord=use_ord, hist=hist,
                        m_num=m_num)
                if use_ord or carries:
                    row_counts_np = row_counts_np.copy()
                    row_counts_np[0] -= drop   # dropped rows were leaf 0

    with obs.span("repro.forest.assemble", batch=batch):
        return _assemble_tree(acc, max_arity, m_num, task), stats_log


# ---------------------------------------------------------------------------
# The batched forest driver (vmap over tree state — DESIGN.md §3; the
# manager's parallel tree-builder queries answered by ONE device program)
# ---------------------------------------------------------------------------

def build_forest(
    *,
    num: jnp.ndarray, cat: jnp.ndarray, labels: jnp.ndarray,
    sorted_vals: jnp.ndarray, sorted_idx: jnp.ndarray,
    arities: tuple[int, ...], num_classes: int,
    params: TreeParams, seed: int, tree_indices,
    collect_stats: bool = False,
    bin_of: Optional[jnp.ndarray] = None,
    bin_edges: Optional[jnp.ndarray] = None,
    engine: Optional[SplitEngine] = None,
    cat_engine: Optional[SplitEngine] = None,
) -> tuple[list[Tree], list[list[LevelStats]]]:
    """Train a BATCH of trees with one fused jitted program per depth level.

    Trees are independent, so the whole fused level step is vmapped over a
    leading tree axis (DESIGN.md §3): per-tree PRNG keys, per-tree bootstrap
    row weights, and the per-tree leaf frontier padded to the batch maximum
    `Lp`, with trees that finish early masked via all-False `splittable`
    rows.  For T trees of depth D this issues D device programs total where
    the per-tree builder issues T·D — the dispatch/host-sync amortization
    that fills the machine at small-to-medium n.  Mesh engines
    (`level.sharded`) are batch-native: their shard_map'd search runs once
    per level on the stacked tree state, so SHARDED training keeps the same
    D-dispatch shape (see `level.plan._fused_level_step_batched`).

    The host loop is PIPELINED: after dispatching level d the driver first
    runs level d−1's deferred bookkeeping (`_grow_level`, node values) —
    overlapping it with the device executing level d — and only then blocks
    on level d's struct (whose D2H transfer was started eagerly with
    `copy_to_host_async`).  Bookkeeping order per tree is unchanged, so
    results are bit-identical to the unpipelined loop.

    Bit-parity: each returned tree is IDENTICAL to what
    `build_tree(..., tree_idx=t)` — and hence `build_tree_reference` —
    produces for the same (seed, t), for every backend and engine.
    Asserted by tests/test_forest_batch.py and tests/test_distributed.py.

    Args are as `build_tree`, except `tree_indices` (an iterable of tree
    ids, each seeding its own bagging/candidate streams) replaces
    `tree_idx`, and legacy `supersplit_fn` closures are not accepted
    (pass a `level.SplitEngine` via `engine=` instead).  Sprint pruning
    (`prune_closed_frac`) IS supported: rows closed in EVERY tree of the
    batch are dropped (a result-invariant subset of each tree's closed
    rows), keeping n divisible by any mesh engine's row-shard width.

    Returns (trees, stats_logs), parallel lists over `tree_indices`.
    """
    plan, (n, m_num, m_cat, m, max_arity, m_prime) = _make_plan(
        params, sorted_vals=sorted_vals, arities=arities, labels=labels,
        num_classes=num_classes, engine=engine, cat_engine=cat_engine)
    if isinstance(plan.numeric, LegacyFn):
        raise ValueError(
            "legacy supersplit_fn closures are per-tree only; pass a "
            "level.SplitEngine (engine=...) or use build_tree")
    task = params.task
    hist = params.split_mode == "hist"
    # device-put the (possibly host-lazy, see dataset.from_numpy) shared
    # inputs once, before the level loop
    num, cat, labels = jnp.asarray(num), jnp.asarray(cat), jnp.asarray(labels)
    sorted_vals = jnp.asarray(sorted_vals)
    sorted_idx = jnp.asarray(sorted_idx)
    # the bucket state is tree-independent (quantized once per forest):
    # shared read-only input of the batched step, like the presorted order
    bin_of, bin_edges = _hist_state(num, sorted_vals, params, m_num,
                                    bin_of, bin_edges)
    num = _level_num(num)
    carries = plan.carries_tables       # hist subtraction (DESIGN.md §6)
    edges_np = np.asarray(bin_edges) if plan.use_bin_cuts else None
    tidx = [int(t) for t in tree_indices]
    T = len(tidx)
    assert T >= 1

    # per-tree stacked device state: bootstrap weights, stats, PRNG keys
    w = bagging.bag_counts_forest(seed, jnp.asarray(tidx, jnp.int32), n,
                                  params.bagging)                   # (T, n)
    stats = jax.vmap(
        lambda ww: splits.row_stats(labels, ww, num_classes, task))(w)
    S_dim = int(stats.shape[-1])
    base_key = jax.random.PRNGKey(seed ^ 0x5EED)
    fkeys = jax.vmap(lambda t: jax.random.fold_in(base_key, t))(
        jnp.asarray(tidx, jnp.int32))

    def cnt_np(t):
        return t.sum(-1) if task == "classification" else t[..., 0]

    accs = [_NodeAccum(num_classes, task) for _ in range(T)]
    open_nodes = [[a.new_node(0)] for a in accs]  # per tree: leaf h -> node
    leaf_of = jnp.ones((T, n), jnp.int32)
    stats_logs: list[list[LevelStats]] = [[] for _ in range(T)]

    use_ord = plan.use_ord
    # every tree starts at the root, where value order == (leaf, value)
    # order, so the initial per-tree leaf order is the shared presort
    ord_idx = (jnp.broadcast_to(sorted_idx[None], (T,) + sorted_idx.shape)
               if use_ord else jnp.zeros((T, 0, 0), jnp.int32))

    def write_values(Ls_d, counts_d, totals_d):
        """Node values of one level from its (host) leaf totals."""
        for t in range(T):
            for h in range(1, Ls_d[t] + 1):
                accs[t].set_value(open_nodes[t][h - 1], totals_d[t, h],
                                  counts_d[t, h], task)

    def make_book(depth_d, Ls_d, counts_d, totals_d, host_d, part_d, n_d):
        """Level d's deferred host bookkeeping (runs after dispatching
        level d+1; ordering per tree is exactly the unpipelined loop's)."""
        def book():
            write_values(Ls_d, counts_d, totals_d)
            for t in range(T):
                L = Ls_d[t]
                if L == 0 or not part_d[t]:
                    continue
                host_t = {k: host_d[k][t] for k in
                          ("best_feat", "best_gain", "thr", "mask",
                           "will_split")}
                next_open, any_split = _grow_level(
                    accs[t], open_nodes[t], host_t, L, m_num, depth_d,
                    edges_np=edges_np)
                if collect_stats:
                    # per-tree accounting under the tree's OWN padding, so
                    # the counters match a per-tree build of the same tree
                    Lp_t = _pad_leaves(L, params.leaf_pad)
                    open_w = float(counts_d[t, 1:L + 1].sum())
                    passes = int(min(m_prime * (1 if params.usb else L), m))
                    tbl_w = ((Lp_t // 2 + 1) if carries and depth_d > 0
                             else (Lp_t + 1))
                    stats_logs[t].append(LevelStats(
                        depth=depth_d, open_leaves=L,
                        network_bits_bitmap=int(open_w),
                        network_bits_supersplit=int(m * (Lp_t + 1) * 64),
                        class_list_bits=class_list.storage_bits(n_d, L),
                        feature_passes=passes, rows_scanned=n_d * passes,
                        hist_table_bytes=(m_num * tbl_w * params.num_bins
                                          * S_dim * 4 if hist else 0)))
                if any_split:
                    open_nodes[t] = next_open
        return book

    totals_np = None                      # (T, width, S), host
    row_counts_np = None                  # (T, width), host (ord layout)
    Ls = [1] * T                          # current frontier size per tree
    closed_np = 0                         # rows closed in EVERY tree
    pending = None                        # previous level's deferred book()
    tables = None                         # carried hist tables (device, T)
    maps_src = None                       # (ws, key_counts, Ls) of level-1
    no_tables = jnp.zeros((T, 0, 0, 0, 0), jnp.float32)
    no_map = jnp.zeros((T, 0), jnp.int32)
    batch = f"{tidx[0]}:{tidx[-1] + 1}"     # the trees, as a span arg
    for depth in range(params.max_depth + 1):
        if max(Ls) == 0:
            break
        Lp = _pad_leaves(max(Ls), params.leaf_pad)  # batch-max frontier

        with obs.span("repro.level.prep", depth=depth, batch=batch):
            # carry the leaf totals into the new padding (root: once)
            if totals_np is None:
                with obs.span("repro.level.fetch", depth=depth, batch=batch):
                    # waits for the presort / quantize programs too
                    totals_np = np.asarray(jax.vmap(
                        lambda lf, st, ww: _leaf_totals(lf, st, ww, Lp))(
                            leaf_of, stats, w))
                row_counts_np = np.zeros((T, Lp + 1), np.int32)
                row_counts_np[:, 1] = n
            else:
                cur = np.zeros((T, Lp + 1, totals_np.shape[-1]), np.float32)
                k = min(Lp + 1, totals_np.shape[1])  # rows past a tree's
                cur[:, :k] = totals_np[:, :k]        # frontier are zero
                totals_np = cur
                cur_rc = np.zeros((T, Lp + 1), np.int32)
                k = min(Lp + 1, row_counts_np.shape[1])
                cur_rc[:, :k] = row_counts_np[:, :k]
                row_counts_np = cur_rc
            counts = cnt_np(totals_np)                # (T, Lp+1)

            # the splittable frontier mask (per-tree node VALUES are
            # written by the deferred bookkeeping — not needed to dispatch)
            at_max_depth = depth >= params.max_depth
            splittable_p = np.zeros((T, Lp + 1), bool)
            participate = [False] * T
            if not at_max_depth:
                for t in range(T):
                    if Ls[t] == 0:
                        continue
                    sp = counts[t, 1:Ls[t] + 1] >= 2 * params.min_records
                    if sp.any():
                        splittable_p[t, 1:Ls[t] + 1] = sp
                        participate[t] = True
            if not splittable_p.any():
                # nothing to dispatch: drain the pipeline, write the final
                # frontier's node values, stop
                with obs.span("repro.level.book", depth=depth, batch=batch):
                    if pending is not None:
                        pending()
                        pending = None
                    write_values(Ls, counts, totals_np)
                Ls = [0] * T
                break

            # Sprint pruning (paper §3), batched: drop rows closed in EVERY
            # tree once they dominate (core/pruning.py).  Runs between
            # levels (before dispatch), so the ord layout is always current
            # here — the only level whose partition is skipped is the last
            # one before max_depth, and that iteration breaks above instead
            # of reaching this point.  The common-closed count rode home in
            # the previous level's struct (`closed_rows`), so the trigger
            # costs no extra dispatch or host sync and the pipelining stays
            # intact.
            if params.prune_closed_frac < 1.0 and n > 0:
                drop = pruning.plan_drop(n, closed_np, plan.row_shards,
                                         params.prune_closed_frac)
                if drop:
                    keep_open = (leaf_of > 0).any(axis=0)      # (n,) device
                    (n, leaf_of, ord_idx, sorted_vals, sorted_idx, bin_of,
                     num, cat, stats, w, labels) = pruning.compact_rows(
                        keep=pruning.keep_mask(~keep_open, drop), drop=drop,
                        leaf_of=leaf_of, ord_idx=ord_idx,
                        sorted_vals=sorted_vals, sorted_idx=sorted_idx,
                        bin_of=bin_of, num=num, cat=cat, stats=stats, w=w,
                        labels=labels, use_ord=use_ord, hist=hist,
                        m_num=m_num)
                    if use_ord or carries:
                        row_counts_np = row_counts_np.copy()
                        row_counts_np[:, 0] -= drop  # dropped rows: leaf 0
                    closed_np -= drop

            # histogram subtraction: per-tree maps from the previous
            # level's split bitmap + child row counts (smaller child =
            # build slot)
            subtract = bool(carries and tables is not None
                            and maps_src is not None)
            if subtract:
                ws_prev, kc_prev, Ls_prev = maps_src
                parent_b = np.zeros((T, Lp + 1), np.int32)
                sib_b = np.zeros((T, Lp + 1), np.int32)
                slot_b = np.zeros((T, Lp + 1), np.int32)
                for t in range(T):
                    if Ls_prev[t]:
                        parent_b[t], sib_b[t], slot_b[t] = _child_maps(
                            ws_prev[t], kc_prev[t], Ls_prev[t], Lp)
                maps_dev = (tables, jnp.asarray(parent_b),
                            jnp.asarray(sib_b), jnp.asarray(slot_b))
            else:
                maps_dev = (no_tables, no_map, no_map, no_map)

        # the whole level of the whole batch on device: ONE dispatch,
        # one stacked struct back
        obs.count("level.dispatches")
        obs.count("level.tree_rows", sum(participate) * n)
        obs.count("level.table_updates",
                  sum(participate) * plan.table_updates(n, subtract))
        with obs.span("repro.level.dispatch", depth=depth, batch=batch):
            struct, leaf_of, ord_idx, next_totals, new_tables = \
                _fused_level_step_batched(
                    _zeros_unless(plan.pass_num or not hist, num,
                                  jnp.float32),
                    cat, labels,
                    _zeros_unless(plan.pass_sorted, sorted_vals,
                                  jnp.float32),
                    _zeros_unless(plan.pass_sorted, sorted_idx, jnp.int32),
                    bin_of,
                    _zeros_unless(plan.pass_edges or not hist, bin_edges,
                                  jnp.float32),
                    ord_idx, leaf_of, w, stats,
                    jnp.asarray(splittable_p), jnp.asarray(totals_np),
                    jnp.asarray(row_counts_np), *maps_dev, fkeys,
                    jnp.int32(depth), plan=plan, Lp=Lp,
                    need_partition=use_ord and depth + 1 < params.max_depth,
                    subtract=subtract)
            if carries:
                tables = new_tables
            # pipeline: start the D2H transfer, run the PREVIOUS level's
            # host bookkeeping while the device executes this level, then
            # block
            _start_fetch((struct, next_totals))
        if pending is not None:
            with obs.span("repro.level.book", depth=depth, batch=batch):
                pending()
            pending = None

        totals_cur = totals_np            # this level's totals, for values
        with obs.span("repro.level.fetch", depth=depth, batch=batch):
            host, totals_np = jax.device_get((struct, next_totals))
        obs.count("level.fetch_bytes", _nbytes((host, totals_np)))

        # next frontier sizes need only the split bitmap — the rest of the
        # bookkeeping is deferred to overlap the next dispatch
        with obs.span("repro.level.book", depth=depth, batch=batch):
            if use_ord or carries:
                row_counts_np = host["key_counts"]
            if carries:
                maps_src = (host["will_split"], host["key_counts"], list(Ls))
            closed_np = int(host["closed_rows"])
            ws = host["will_split"]
            Ls_next = [0] * T
            for t in range(T):
                if participate[t]:
                    Ls_next[t] = 2 * int(ws[t, 1:Ls[t] + 1].sum())
            pending = make_book(depth, list(Ls), counts, totals_cur, host,
                                list(participate), n)
            Ls = Ls_next

    if pending is not None:               # safety drain (loop always breaks
        pending()                         # via the no-dispatch path above)

    with obs.span("repro.forest.assemble", batch=batch):
        return ([_assemble_tree(a, max_arity, m_num, task) for a in accs],
                stats_logs)


# ---------------------------------------------------------------------------
# The out-of-core streamed forest driver (DESIGN.md §8)
# ---------------------------------------------------------------------------

def build_forest_streamed(
    *,
    source,
    params: TreeParams, seed: int, tree_indices,
    collect_stats: bool = False,
    engine: Optional[SplitEngine] = None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 1,
    resume: bool = False,
    _checkpointer=None,
) -> tuple[list[Tree], list[list[LevelStats]]]:
    """Train a batch of hist-mode trees from a `dataset.RowSource`.

    The dataset never exists on device (nor, for `MemmapRowSource`, in
    host memory): per depth level the driver streams fixed-shape row
    chunks of the bit-packed bin cache through the jitted
    `_stream_chunk_step`, which replays the previous level's winning
    conditions on the chunk and folds it into the engine's per-leaf
    (feature, bin, stat) table accumulator.  One `_stream_finalize_step`
    merges the accumulator (the sharded engine's single per-level psum)
    and one `_stream_score_step` runs the exact `_level_step_core`
    candidate/score/winner arithmetic on the tables alone.  Leaf
    assignments live in a HOST (T, n) int32 array, written back chunk by
    chunk — peak device memory is bounded by the chunk size and the table
    width, independent of n.

    Restrictions (clear errors below): hist split mode only (exact needs
    the presort; only hist streams), classification only (integer-valued
    tables make chunked accumulation exact), numeric columns only, and
    the source's bucket budget must match `params.num_bins`.  Poisson /
    multinomial bagging draws the per-tree (n,) bootstrap weights on
    device once (the one n-sized transient, transferred to host
    immediately); `bagging="none"` streams with strictly chunk-bounded
    device memory.

    Bit-parity: produces node-for-node the trees of `build_forest` on the
    same quantized state for every chunk size, asserted by
    tests/test_stream_parity.py.

    Fault tolerance (DESIGN.md §9): with `checkpoint_dir=` the driver
    writes an atomic level snapshot of the host-side state every
    `checkpoint_every` completed levels (`repro.core.checkpoint`), and
    `resume=True` restarts from the last snapshot — or returns the
    finished trees immediately if this batch already completed —
    node-for-node bit-identical to an uninterrupted fit, because every
    remaining level replays the same pure chunk reads through the same
    programs.  Chunk reads are retried with exponential backoff on
    transient `OSError`s; a persistent failure flushes the held
    snapshot and raises `dataset.StreamReadError`.

    Returns (trees, stats_logs), parallel lists over `tree_indices`.
    """
    from repro.core import checkpoint as checkpoint_lib
    from repro.core import dataset as dataset_lib
    from repro.core.dataset import RowSource
    from repro.core.level.plan import (_stream_chunk_step,
                                       _stream_finalize_step,
                                       _stream_score_step)
    if not isinstance(source, RowSource):
        raise TypeError(
            f"build_forest_streamed needs a dataset.RowSource, got "
            f"{type(source).__name__} — wrap the data with "
            f"ArrayRowSource.from_dataset / MemmapRowSource.build")
    if params.split_mode != "hist":
        raise ValueError(
            "streaming training requires split_mode='hist': exact mode "
            "needs the full presorted order, which cannot be built from a "
            "disk-backed source (exact needs the presort; only hist "
            "streams)")
    if params.task != "classification" or source.task != "classification":
        raise ValueError(
            "streaming training is classification-only: its chunked table "
            "accumulation is exact because classification tables hold "
            "integer-valued counts; regression y-sums could drift")
    if source.m_num < 1:
        raise ValueError("streaming training needs >= 1 numeric column")
    if source.num_bins != params.num_bins:
        raise ValueError(
            f"RowSource was quantized with num_bins={source.num_bins} but "
            f"TreeParams has num_bins={params.num_bins} — rebuild the "
            f"source or match the params")

    ck = _checkpointer
    if ck is None and checkpoint_dir is not None:
        ck = checkpoint_lib.StreamCheckpointer(checkpoint_dir,
                                               every=checkpoint_every)
        ck.prepare(source=source, params=params, seed=seed, resume=resume)
    if ck is not None and resume:
        done = ck.load_batch(tree_indices)
        if done is not None:        # batch committed by a previous run
            return done

    # subtraction is a no-op under fixed-shape chunks (every chunk is
    # scanned anyway), and PR 5 proved subtract == plain bit-identical,
    # so the streamed plan always runs the plain table build
    params_pl = dataclasses.replace(params, hist_subtract=False)
    m_num = source.m_num
    m_prime = params.num_candidates or max(
        1, math.isqrt(m_num) + (0 if math.isqrt(m_num) ** 2 == m_num else 1))
    plan = make_plan(params_pl, m_num=m_num, m_cat=0, max_arity=1,
                     num_classes=source.num_classes, m_prime=m_prime,
                     engine=engine)
    if not getattr(plan.numeric, "supports_stream", False):
        raise ValueError(
            f"engine {plan.numeric!r} does not support chunked "
            f"accumulation (supports_stream)")
    task = params.task
    num_classes = source.num_classes
    n = source.n
    statics = plan.statics
    edges_np = source.edges
    tidx = [int(t) for t in tree_indices]
    T = len(tidx)
    assert T >= 1

    # host-resident per-row state: labels, bootstrap weights, leaf ids
    labels_np = np.ascontiguousarray(source.labels, np.int32)
    if params.bagging == "none":
        w_np = np.ones((T, n), np.float32)
    else:
        # per-tree draws (bit-identical to bag_counts_forest), fetched to
        # host one at a time — the single n-sized device transient
        w_np = np.empty((T, n), np.float32)
        for i, t in enumerate(tidx):
            w_np[i] = np.asarray(bagging.bag_counts(seed, t, n,
                                                    params.bagging))
    base_key = jax.random.PRNGKey(seed ^ 0x5EED)
    fkeys = jax.vmap(lambda t: jax.random.fold_in(base_key, t))(
        jnp.asarray(tidx, jnp.int32))

    accs = [_NodeAccum(num_classes, task) for _ in range(T)]
    open_nodes = [[a.new_node(0)] for a in accs]
    stats_logs: list[list[LevelStats]] = [[] for _ in range(T)]
    leaf_np = np.ones((T, n), np.int32)
    active = None                   # original row ids of the active rows
    n_act = n
    Ls = [1] * T
    start_depth = 0

    rs = plan.row_shards
    chunk = max(1, int(source.chunk_size))
    # previous level's device-side decisions for the chunk reassignment
    dec = (jnp.zeros((T, 1), jnp.int32), jnp.zeros((T, 1), jnp.float32),
           jnp.zeros((T, 1), jnp.int32), jnp.zeros((T, 1), jnp.int32))
    Lpp = 0
    S_dim = num_classes

    if ck is not None and resume:
        snap = ck.load_snapshot(tidx)
        if snap is not None:
            # restore the end-of-level state and re-derive what was not
            # stored: labels come from the source, bag weights from the
            # seeded draws above — both exactly as a fresh fit computes
            # them — then the stored row map compacts them to n_act
            st = checkpoint_lib.unpack_stream_state(
                snap, num_classes=num_classes, task=task)
            start_depth = st["next_depth"]
            Ls, Lpp = st["Ls"], st["Lpp"]
            accs, open_nodes = st["accs"], st["open_nodes"]
            stats_logs = st["stats_logs"]
            leaf_np, active = st["leaf"], st["active"]
            n_act = leaf_np.shape[1]
            if active is not None:
                labels_np = np.ascontiguousarray(labels_np[active])
                w_np = np.ascontiguousarray(w_np[:, active])
            dec = tuple(jnp.asarray(d) for d in st["dec"])

    retry_kw = dict(attempts=source.retry_attempts,
                    base_delay=source.retry_base_delay,
                    max_delay=source.retry_max_delay,
                    sleep=source.retry_sleep)

    for depth in range(start_depth, params.max_depth + 1):
        if max(Ls) == 0:
            break
        Lp = _pad_leaves(max(Ls), params.leaf_pad)
        at_max_depth = depth >= params.max_depth
        need_tables = not at_max_depth
        root = depth == 0

        # --- chunk pass: reassign + accumulate --------------------------
        if need_tables:
            acc_dev = plan.numeric.stream_init(T, statics, Lp)
        else:       # terminal level: per-leaf stat totals only
            acc_dev = jnp.zeros((T, Lp + 1, S_dim), jnp.float32)
        # fixed-shape chunk buffers, padded to a row-shard multiple (pad
        # rows ride with w = 0 / leaf 0 and contribute exactly zero)
        C_buf = max(rs, -(-min(chunk, max(n_act, 1)) // rs) * rs)
        bins_buf = np.zeros((m_num, C_buf),
                            np.dtype(presort.bin_dtype(params.num_bins)))
        labels_buf = np.zeros((C_buf,), np.int32)
        w_buf = np.zeros((T, C_buf), np.float32)
        leaf_buf = np.zeros((T, C_buf), np.int32)
        for lo in range(0, n_act, C_buf):
            hi = min(lo + C_buf, n_act)
            c = hi - lo
            try:
                with obs.span("repro.stream.read", depth=depth, row=lo):
                    block = dataset_lib.read_with_retry(
                        *((source.bins_block, lo, hi) if active is None
                          else (source.bins_take, active[lo:hi])),
                        **retry_kw)
            except dataset_lib.StreamReadError:
                if ck is not None:  # persist the last completed level so
                    ck.flush()      # the resume loses only this one
                raise
            with obs.span("repro.stream.stage", depth=depth, row=lo):
                if c < C_buf:       # zero the pad of the final chunk
                    bins_buf[:, c:] = 0
                    labels_buf[c:] = 0
                    w_buf[:, c:] = 0.0
                    leaf_buf[:, c:] = 0
                bins_buf[:, :c] = block
                labels_buf[:c] = labels_np[lo:hi]
                w_buf[:, :c] = w_np[:, lo:hi]
                leaf_buf[:, :c] = leaf_np[:, lo:hi]
            obs.count("stream.chunk_dispatches")
            if need_tables:
                obs.count("level.table_updates",
                          T * plan.table_updates(C_buf, False))
            with obs.span("repro.stream.dispatch", depth=depth, row=lo):
                leaf_c, acc_dev = _stream_chunk_step(
                    bins_buf, labels_buf, w_buf, leaf_buf, *dec, acc_dev,
                    plan=plan, Lp=Lp, Lpp=Lpp, root=root,
                    need_tables=need_tables)
            with obs.span("repro.stream.fetch", depth=depth, row=lo):
                leaf_np[:, lo:hi] = np.asarray(leaf_c)[:, :c]

        with obs.span("repro.stream.score", depth=depth):
            # --- finalize: merged tables + per-leaf totals ---------------
            if need_tables:
                merged, totals_dev = _stream_finalize_step(acc_dev,
                                                           plan=plan)
                totals_np = np.asarray(totals_dev)
            else:
                merged, totals_np = None, np.asarray(acc_dev)
            counts = totals_np.sum(-1)                    # classification

            for t in range(T):
                for h in range(1, Ls[t] + 1):
                    accs[t].set_value(open_nodes[t][h - 1], totals_np[t, h],
                                      counts[t, h], task)

            splittable_p = np.zeros((T, Lp + 1), bool)
            if not at_max_depth:
                for t in range(T):
                    if Ls[t]:
                        splittable_p[t, 1:Ls[t] + 1] = \
                            counts[t, 1:Ls[t] + 1] >= 2 * params.min_records
            if not splittable_p.any():
                break                     # values already written

            # --- score: one program on the tables alone ------------------
            res = _stream_score_step(merged, jnp.asarray(splittable_p),
                                     fkeys, jnp.int32(depth), plan=plan,
                                     Lp=Lp)
            host = jax.device_get({k: res[k] for k in
                                   ("best_feat", "best_gain", "thr",
                                    "will_split")})
            dec = (res["feat_of_leaf"], res["thr"], res["new_left"],
                   res["new_right"])
            Lpp = Lp

        with obs.span("repro.level.book", depth=depth):
            ws = host["will_split"]
            no_mask = np.zeros((Lp + 1, 1), bool)             # numeric-only
            Ls_next = [0] * T
            for t in range(T):
                if Ls[t] == 0:
                    continue
                host_t = {k: host[k][t] for k in
                          ("best_feat", "best_gain", "thr", "will_split")}
                host_t["mask"] = no_mask
                next_open, any_split = _grow_level(
                    accs[t], open_nodes[t], host_t, Ls[t], m_num, depth,
                    edges_np=edges_np)
                if collect_stats:
                    Lp_t = _pad_leaves(Ls[t], params.leaf_pad)
                    passes = int(min(m_prime * (1 if params.usb else Ls[t]),
                                     m_num))
                    stats_logs[t].append(LevelStats(
                        depth=depth, open_leaves=Ls[t],
                        network_bits_bitmap=int(counts[t, 1:Ls[t] + 1].sum()),
                        network_bits_supersplit=int(m_num * (Lp_t + 1) * 64),
                        class_list_bits=class_list.storage_bits(n_act, Ls[t]),
                        feature_passes=passes, rows_scanned=n_act * passes,
                        hist_table_bytes=m_num * (Lp_t + 1) * params.num_bins
                        * S_dim * 4))
                if any_split:
                    open_nodes[t] = next_open
                Ls_next[t] = 2 * int(ws[t, 1:Ls[t] + 1].sum())
            Ls = Ls_next

            # --- Sprint pruning, HOST-side: drop rows closed in every
            # tree (result-invariant; fixed-shape padded chunks need no
            # divisibility)
            if params.prune_closed_frac < 1.0 and n_act > 0 and max(Ls) > 0:
                open_any = (leaf_np > 0).any(axis=0)
                closed = n_act - int(open_any.sum())
                if closed > 0 and closed / n_act >= params.prune_closed_frac:
                    keep = np.flatnonzero(open_any)
                    active = keep if active is None else active[keep]
                    leaf_np = np.ascontiguousarray(leaf_np[:, keep])
                    w_np = np.ascontiguousarray(w_np[:, keep])
                    labels_np = np.ascontiguousarray(labels_np[keep])
                    n_act = len(keep)

        # end-of-level state, post-bookkeeping.  The final level's snapshot
        # is never written: finish_batch commits the trees immediately
        # after the loop, so its only possible consumer is a crash in that
        # gap — which the PREVIOUS snapshot already covers (one level of
        # recompute), and skipping it saves a write on every batch.
        if ck is not None and depth < params.max_depth:
            ck.save_snapshot(tidx, depth, checkpoint_lib.pack_stream_state(
                tidx=tidx, depth=depth, Ls=Ls, leaf_np=leaf_np,
                active=active, dec=dec, Lpp=Lpp, accs=accs,
                open_nodes=open_nodes, stats_logs=stats_logs))

    with obs.span("repro.forest.assemble"):
        trees = [_assemble_tree(a, 1, m_num, task) for a in accs]
    if ck is not None:
        ck.finish_batch(tidx, trees, stats_logs)
    return trees, stats_logs


# ---------------------------------------------------------------------------
# Prediction
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m_num", "iters"))
def _predict_jit(feature, threshold, is_cat, cat_mask, children, value,
                 num, cat, m_num, iters):
    B = num.shape[0] if num.size else cat.shape[0]
    node = jnp.zeros((B,), jnp.int32)

    def body(_, node):
        f = feature[node]
        leaf = f < 0
        jn = jnp.clip(f, 0, max(m_num - 1, 0))
        jc = jnp.clip(f - m_num, 0, max(cat.shape[1] - 1, 0))
        xnum = (jnp.take_along_axis(num, jn[:, None], 1)[:, 0]
                if num.size else jnp.zeros((B,), jnp.float32))
        xcat = (jnp.take_along_axis(cat, jc[:, None], 1)[:, 0]
                if cat.size else jnp.zeros((B,), jnp.int32))
        go_left = jnp.where(is_cat[node], cat_mask[node, xcat],
                            xnum <= threshold[node])
        nxt = jnp.where(go_left, children[node, 0], children[node, 1])
        return jnp.where(leaf, node, nxt)

    node = jax.lax.fori_loop(0, iters, body, node)
    return value[node]


def __getattr__(name):
    # `build_tree_reference` lives in repro.core.reference (which imports
    # this module); resolve it lazily to keep the historical
    # `tree.build_tree_reference` entry point without an import cycle.
    if name == "build_tree_reference":
        from repro.core.reference import build_tree_reference
        return build_tree_reference
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
