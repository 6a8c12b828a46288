"""Pallas TPU kernel for the Alg. 1 supersplit scan (the DRF hot loop).

GPU/CPU papers stream rows one at a time (Alg. 1's `for (a,y,i) in q(j)`);
a TPU wants the same *semantics* re-blocked for the MXU/VPU and the
HBM→VMEM hierarchy.  The adaptation (DESIGN.md §2):

  * grid = (feature group, leaf block, row block): row blocks stream
    sequentially (one HBM→VMEM pass per column per leaf block — the
    paper's "read sequentially, no random access").  A feature group is 8
    columns, so a row block is an (8, Bn) tile; leaf blocks are 128 or
    256 leaf slots, and leaves are independent, so they tile freely,
  * rows lie on the lane axis throughout; per-leaf quantities are
    (leaf, row) arrays and per-leaf carries are columns,
  * the per-leaf histogram state H ∈ (L+1, S), last-seen value v, and
    running best (gain, threshold) live in VMEM scratch and persist across
    row blocks (the scan carry),
  * within a block the sequential dependence is broken with an EXCLUSIVE
    per-leaf prefix computed as one strictly-upper-triangular matmul
    (S·Wb, Bn) @ (Bn, Bn) — MXU work instead of a serial loop,
  * the "previous in-bag value per leaf" needs a running max, computed with
    log2(Bn) lane-rotate max steps (VPU).

Exactness: identical split choices to `repro.core.splits.best_numeric_split_scan`
up to float summation order (verified in tests against ref.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = float("-inf")  # plain float: Pallas kernels must not capture array consts
_VMEM_LIMIT_BYTES = 48 << 20
_HIGHEST = jax.lax.Precision.HIGHEST


def _impurity(h: list, kind: str) -> jnp.ndarray:
    """Weighted (N·) impurity for stats given as a list of S rows."""
    if kind == "gini":
        n = sum(h[1:], h[0])
        sq = sum((x * x for x in h[1:]), h[0] * h[0])
        return n - jnp.where(n > 0, sq / jnp.maximum(n, 1e-12), 0.0)
    if kind == "entropy":
        n = sum(h[1:], h[0])
        nn = jnp.maximum(n, 1e-12)
        terms = [jnp.where(x > 0, (x / nn) * jnp.log(jnp.maximum(x / nn, 1e-12)),
                           0.0) for x in h]
        return -(n * sum(terms[1:], terms[0]))
    if kind == "variance":
        w, wy, wy2 = h
        return jnp.maximum(wy2 - jnp.where(w > 0, wy * wy / jnp.maximum(w, 1e-12), 0.0), 0.0)
    raise ValueError(kind)


def _count(h: list, task: str) -> jnp.ndarray:
    return sum(h[1:], h[0]) if task == "classification" else h[0]


def _stat_rows(y, w, s_dim, task):
    """Per-row stats as S rows of shape (1, Bn)."""
    if task == "classification":
        yi = y.astype(jnp.int32)
        return [jnp.where(yi == s, w, 0.0) for s in range(s_dim)]
    return [w, w * y, w * y * y]


def _excl_cummax(m: jnp.ndarray) -> jnp.ndarray:
    """Exclusive running max along the lane (row) axis via log-steps."""
    bn = m.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, m.shape, 1)
    out = jnp.where(lane >= 1, pltpu.roll(m, 1, 1), NEG)
    shift = 1
    while shift < bn:
        out = jnp.maximum(out, jnp.where(lane >= shift,
                                         pltpu.roll(out, shift, 1), NEG))
        shift *= 2
    return out


def _column_to_row(col: jnp.ndarray) -> jnp.ndarray:
    """(Wb, 1) -> (1, Wb) through an aligned (Wb, 128) transpose."""
    return jnp.broadcast_to(col, (col.shape[0], 128)).T[0:1, :]


def _split_scan_kernel(vals_ref, leaf_ref, w_ref, y_ref, info_ref,
                       gain_ref, thr_ref,
                       h_scr, v_scr, bs_scr, bt_scr,
                       *, fb: int, wb: int, s_dim: int, nblocks: int,
                       impurity: str, task: str, min_records: float):
    """One (feature group, leaf block, row block) grid step."""
    lb = pl.program_id(1)
    jb = pl.program_id(2)
    bn = vals_ref.shape[1]

    @pl.when(jb == 0)
    def _init():
        h_scr[...] = jnp.zeros(h_scr.shape, jnp.float32)
        v_scr[...] = jnp.full(v_scr.shape, NEG, jnp.float32)  # none seen
        bs_scr[...] = jnp.full(bs_scr.shape, NEG, jnp.float32)
        bt_scr[...] = jnp.zeros(bt_scr.shape, jnp.float32)

    slots = jax.lax.broadcasted_iota(jnp.int32, (wb, bn), 0) + lb * wb
    r = jax.lax.broadcasted_iota(jnp.int32, (s_dim * wb, bn), 0)
    srow = jnp.zeros_like(r)
    for s in range(1, s_dim):
        srow = srow + (r >= s * wb).astype(jnp.int32)
    rslot = r - srow * wb + lb * wb
    upper = (jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0)
             < jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 1)
             ).astype(jnp.float32)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (wb, bn), 1)

    for f in range(fb):
        vals = vals_ref[f:f + 1, :]                           # (1, Bn)
        leaf = leaf_ref[f:f + 1, :]
        w = w_ref[f:f + 1, :]
        y = y_ref[f:f + 1, :]
        in_leaf = slots == leaf                               # (Wb, Bn)
        onehot = in_leaf.astype(jnp.float32)
        inbag = (w > 0) & (leaf > 0)
        # per-row (totals, cand) of the row's leaf, gathered by a one-hot
        # contraction (TPU-friendly, no gather); rows outside the leaf
        # block read zeros and stay inactive
        info = jax.lax.dot(info_ref[f], onehot, precision=_HIGHEST,
                           preferred_element_type=jnp.float32)
        active = inbag & (info[s_dim:s_dim + 1, :] > 0)
        st = _stat_rows(y, w, s_dim, task)
        sel = st[0]
        for s in range(1, s_dim):
            sel = jnp.where(srow == s, st[s], sel)
        contrib = jnp.where((rslot == leaf) & active, sel, 0.0)  # (S·Wb, Bn)

        # exclusive per-leaf prefix within the block: strictly upper
        # triangular matmul over the row axis
        left_full = h_scr[f] + jax.lax.dot(
            contrib, upper, precision=_HIGHEST,
            preferred_element_type=jnp.float32)
        left = [jnp.sum(left_full[s * wb:(s + 1) * wb] * onehot, axis=0,
                        keepdims=True) for s in range(s_dim)]  # S × (1, Bn)
        right = [info[s:s + 1, :] - left[s] for s in range(s_dim)]

        # previous in-bag value per leaf (values ascend within a column)
        mvals = jnp.where(in_leaf & inbag, vals, NEG)         # (Wb, Bn)
        v_carry = v_scr[f]                                    # (Wb, 1)
        pv_all = jnp.maximum(_excl_cummax(mvals), v_carry)
        pv = jnp.max(jnp.where(in_leaf, pv_all, NEG), axis=0, keepdims=True)

        tau = (vals + pv) * 0.5
        parent = [left[s] + right[s] for s in range(s_dim)]
        gain = (_impurity(parent, impurity) - _impurity(left, impurity)
                - _impurity(right, impurity))
        ok = active & (vals > pv) & (pv > NEG) \
            & (_count(left, task) >= min_records) \
            & (_count(right, task) >= min_records)
        gain = jnp.where(ok, gain, NEG)                       # (1, Bn)

        # per-leaf best within the block, first-row tie-break (scan order)
        gmat = jnp.where(in_leaf, gain, NEG)                  # (Wb, Bn)
        blk_best = jnp.max(gmat, axis=1, keepdims=True)       # (Wb, 1)
        first = jnp.min(jnp.where(gmat >= blk_best, lanes, bn), axis=1,
                        keepdims=True)
        blk_thr = jnp.sum(jnp.where(lanes == first, tau, 0.0), axis=1,
                          keepdims=True)

        best = bs_scr[f]
        better = blk_best > best
        bs_scr[f] = jnp.where(better, blk_best, best)
        bt_scr[f] = jnp.where(better, blk_thr, bt_scr[f])

        # carry updates
        h_scr[f] = h_scr[f] + jnp.sum(contrib, axis=1, keepdims=True)
        v_scr[f] = jnp.maximum(v_carry,
                               jnp.max(mvals, axis=1, keepdims=True))

    @pl.when(jb == nblocks - 1)
    def _emit():
        for f in range(fb):
            gain_ref[f:f + 1, :] = _column_to_row(bs_scr[f])
            thr_ref[f:f + 1, :] = _column_to_row(bt_scr[f])


def block_plan(m: int, L1: int) -> tuple[int, int, int, int]:
    """(fb, mp, wb, L1p): feature-group size and padded column count,
    leaf-block size and padded leaf-slot count.  A group is all m columns
    when m <= 8, else 8; a leaf block is 128 slots, or 256 past 128."""
    fb = m if m <= 8 else 8
    wb = 128 if L1 <= 128 else 256
    return fb, m + (-m) % fb, wb, L1 + (-L1) % wb


@functools.partial(
    jax.jit,
    static_argnames=("L1", "s_dim", "bn", "impurity", "task", "min_records",
                     "interpret"))
def split_scan_pallas(
    vals: jnp.ndarray,     # (m, n) sorted values per feature
    leaf: jnp.ndarray,     # (m, n) int32 leaf ids in sorted order
    w: jnp.ndarray,        # (m, n) bag weights in sorted order
    y: jnp.ndarray,        # (m, n) labels in sorted order
    cand: jnp.ndarray,     # (m, L1) float32 candidate mask (leaf 0 = 0)
    totals: jnp.ndarray,   # (m, L1, S) global per-leaf stat totals
    *, L1: int, s_dim: int, bn: int,
    impurity: str, task: str, min_records: float, interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Best (gain, threshold) per (feature, leaf): (m, L1) each."""
    m, n = vals.shape
    assert n % bn == 0, f"n={n} must be a multiple of bn={bn} (pad rows)"
    nblocks = n // bn
    fb, mp, wb, L1p = block_plan(m, L1)
    # (m, S+1, L1p): per-leaf totals with the candidate mask as a last row
    info = jnp.concatenate([totals.transpose(0, 2, 1), cand[:, None, :]], 1)
    info = jnp.pad(info, ((0, mp - m), (0, 0), (0, L1p - L1)))
    rows = [jnp.pad(a, ((0, mp - m), (0, 0))) for a in (vals, leaf, w, y)]

    kernel = functools.partial(
        _split_scan_kernel, fb=fb, wb=wb, s_dim=s_dim, nblocks=nblocks,
        impurity=impurity, task=task, min_records=min_records)

    row_spec = pl.BlockSpec((fb, bn), lambda g, l, j: (g, j))
    out_spec = pl.BlockSpec((fb, wb), lambda g, l, j: (g, l))
    gain, thr = pl.pallas_call(
        kernel,
        grid=(mp // fb, L1p // wb, nblocks),
        in_specs=[row_spec, row_spec, row_spec, row_spec,
                  pl.BlockSpec((fb, s_dim + 1, wb),
                               lambda g, l, j: (g, 0, l))],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((mp, L1p), jnp.float32),
                   jax.ShapeDtypeStruct((mp, L1p), jnp.float32)],
        scratch_shapes=[
            # VMEM carries: histogram, last value, best gain, best threshold
            pltpu.VMEM((fb, s_dim * wb, 1), jnp.float32),
            pltpu.VMEM((fb, wb, 1), jnp.float32),
            pltpu.VMEM((fb, wb, 1), jnp.float32),
            pltpu.VMEM((fb, wb, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*rows, info)
    return gain[:m, :L1], thr[:m, :L1]
