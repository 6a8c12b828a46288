"""Pallas TPU kernel for per-leaf count tables (DESIGN.md §6, paper §2.4).

One kernel builds both table kinds the level step needs:

  * `split_mode="hist"`: per-leaf (bin × stat) tables for EVERY numeric
    column from the bit-packed bin cache (uint8 for <= 256 buckets, uint16
    past — presort.bin_dtype), and
  * exact categorical search: per-leaf (category × stat) tables, the
    paper's "attribute value × class → number of records" count tables.

A table entry is T[f, l, v, s] = Σ_rows [leaf = l][x_f = v] · stat_s, so
per row block it factors into ONE matmul per feature,

    (S·Wb, Bn) @ (Bn, Bv)ᵀ  =  (leaf-and-stat one-hot) · (value one-hot)ᵀ,

with rows on the lane axis of both operands (the layout the row state has
in HBM).  The leaf-and-stat operand is built once per row block and shared
by every feature, so the per-row state (leaf ids, bag weights, labels) is
read once per pass, not once per feature.  The output block stays resident
in VMEM across the sequential row-block grid axis and is the accumulator.

Deep or wide tables are tiled over a leaf-block and a value-block grid
axis, sized by `block_plan` so the resident output block fits the VMEM
budget; rows are re-read once per (leaf block, value block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM_TABLE_BYTES = 8 << 20     # resident output block (double-buffered)
_VMEM_LIMIT_BYTES = 48 << 20    # v5e has 128 MiB of VMEM; default scope 16


def block_plan(W: int, V: int, m: int, s_dim: int) -> tuple[int, int]:
    """(wb, bv): leaf-slot and value block sizes for an (m, W, S, V) table.

    bv is the whole value axis rounded up to 128 lanes while it is at most
    512, else 512; wb is the largest multiple of 8 (at most W rounded up to
    8) whose (m, S·wb, bv) f32 output block fits `_VMEM_TABLE_BYTES`.
    """
    vp = V + (-V) % 128
    bv = vp if vp <= 512 else 512
    per_slot = m * s_dim * bv * 4
    wb = max(8, (_VMEM_TABLE_BYTES // per_slot) // 8 * 8)
    return min(wb, W + (-W) % 8), bv


def _hist_kernel(x_ref, leaf_ref, w_ref, y_ref, out_ref, *, m, wb, bv,
                 s_dim, task):
    lb = pl.program_id(0)
    vb = pl.program_id(1)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)

    leaf = leaf_ref[...]                                  # (1, Bn) int32
    w = w_ref[...]
    y = y_ref[...]
    bn = leaf.shape[1]
    # operand rows r = s·wb + l: stat s, leaf slot lb·wb + l
    r = jax.lax.broadcasted_iota(jnp.int32, (s_dim * wb, bn), 0)
    srow = jnp.zeros_like(r)
    for s in range(1, s_dim):
        srow = srow + (r >= s * wb).astype(jnp.int32)
    slot = r - srow * wb + lb * wb
    hit = (slot == leaf) & (w > 0) & (leaf > 0)
    if task == "classification":
        a = jnp.where(hit & (srow == y.astype(jnp.int32)), w, 0.0)
    else:
        a = jnp.where(hit, jnp.where(srow == 0, w,
                                     jnp.where(srow == 1, w * y, w * y * y)),
                      0.0)
    x = x_ref[...].astype(jnp.int32)                      # (m, Bn)
    vals = jax.lax.broadcasted_iota(jnp.int32, (bv, bn), 0) + vb * bv
    for f in range(m):
        onehot = (vals == x[f:f + 1, :]).astype(jnp.float32)   # (Bv, Bn)
        out_ref[f] += jax.lax.dot_general(
            a, onehot, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("W", "V", "s_dim", "bn", "task",
                                             "interpret", "plan"))
def feat_hist_pallas(x, leaf, w, y, *, W, V, s_dim, bn, task, interpret,
                     plan=None):
    """Count tables (m, W, S, V) for ALL m columns in one row pass.

    x: (m, n) value ids (uint8/uint16 bins or int32 categories); leaf/w/y:
    (n,) — shared across columns.  `leaf` entries are scatter SLOTS
    (0 = discard): the subtraction path passes packed build-leaf slots,
    the plain path raw leaf ids.  n must be a multiple of bn;
    `kernels.ops.feature_tables` pads it.  `plan` = (wb, bv) overrides
    `block_plan`, so small tests reach the multi-block tiling.
    """
    m, n = x.shape
    assert n % bn == 0, (n, bn)
    wb, bv = plan or block_plan(W, V, m, s_dim)
    Wp = W + (-W) % wb
    Vp = V + (-V) % bv
    grid = (Wp // wb, Vp // bv, n // bn)
    kernel = functools.partial(_hist_kernel, m=m, wb=wb, bv=bv, s_dim=s_dim,
                               task=task)
    row_spec = pl.BlockSpec((1, bn), lambda l, v, j: (0, j))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((m, bn), lambda l, v, j: (0, j)),
                  row_spec, row_spec, row_spec],
        out_specs=pl.BlockSpec((None, m, s_dim * wb, bv),
                               lambda l, v, j: (l, 0, 0, v)),
        out_shape=jax.ShapeDtypeStruct((Wp // wb, m, s_dim * wb, Vp),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(x, leaf.astype(jnp.int32)[None], w.astype(jnp.float32)[None],
      y.astype(jnp.float32)[None])
    # (lb, m, s, l, v) -> (m, lb·wb + l, s, v)
    out = out.reshape(Wp // wb, m, s_dim, wb, Vp)
    out = out.transpose(1, 0, 3, 2, 4).reshape(m, Wp, s_dim, Vp)
    return out[:, :W, :, :V]
