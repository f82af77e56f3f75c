"""Pallas TPU kernels: rowwise symmetric int8 quantise / dequantise.

Each ROW of a (rows, C) matrix shares one fp32 scale (absmax/127); the
blockwise wire format is the same kernel over (nblocks, BLOCK) rows.
Pure HBM-streaming kernels; the win on TPU is fusing absmax + scale +
round + cast into one VMEM pass (XLA emits two passes: reduce then
binary op).

Tiling (`tiles`): a tile holds at most TILE_BYTES of fp32 input, so the
kernel fits VMEM at any C.  Rows are independent, so the row axis is a
`cdiv` grid: the last row tile may hang over the end, where Pallas masks
the writes and nothing is copied or padded.
  * A row that fits one column tile (C <= MAX_COLS, the common case) is
    quantised in one pass over a (row tiles,) grid.
  * A wider row (a vocabulary-wide C = 151936) streams through ~2048-lane
    column tiles over a (row tiles, phase, column tiles) grid: phase 0
    folds each tile into a running per-row absmax (VMEM scratch), with
    the columns past C masked to zero; phase 1 re-reads the tiles and
    writes q and the scale.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 256               # blockwise wire format: elements per scale
LANES = 128               # TPU lane width
SUBLANES = 32             # row-tile granularity (native int8 tiling)
MAX_COLS = 2048           # widest column tile, in elements
TILE_BYTES = 1 << 20      # fp32 bytes of one input tile

#: Inside a compiled program XLA turns core.compression's `amax / 127.0`
#: into a multiplication by the reciprocal (on CPU and TPU alike); the
#: kernel multiplies by the same constant so its scales are the compiled
#: reference's bit for bit.
_INV127 = 1.0 / 127.0


def tiles(rows: int, cols: int) -> tuple[int, int]:
    """(row tile, column tile) for a (rows, cols) matrix.  The column tile
    is the whole row up to MAX_COLS, else the lane multiple that splits
    the row into the fewest tiles; the row tile fills TILE_BYTES (a
    multiple of SUBLANES), or is every row when they fit."""
    bc = cols
    if cols > MAX_COLS:
        nj = pl.cdiv(cols, MAX_COLS)
        bc = pl.cdiv(pl.cdiv(cols, nj), LANES) * LANES
    lane_cols = pl.cdiv(bc, LANES) * LANES
    br = max(SUBLANES, TILE_BYTES // (4 * lane_cols) // SUBLANES * SUBLANES)
    return min(br, rows), bc


def _write_q(x, amax, q_ref, s_ref):
    scale = amax * _INV127
    safe = jnp.maximum(scale, 1e-12)
    q_ref[...] = jnp.clip(jnp.round(x / safe), -127.0, 127.0).astype(jnp.int8)
    s_ref[...] = scale


def _quant_row_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                     # (br, C)
    _write_q(x, jnp.max(jnp.abs(x), axis=1, keepdims=True), q_ref, s_ref)


def _quant_tiled_kernel(x_ref, q_ref, s_ref, amax_ref, *, cols: int):
    phase, j = pl.program_id(1), pl.program_id(2)
    x = x_ref[...].astype(jnp.float32)                     # (br, bc)

    @pl.when((phase == 0) & (j == 0))
    def _():
        amax_ref[...] = jnp.zeros_like(amax_ref)

    @pl.when(phase == 0)
    def _():
        col = j * x.shape[1] + jax.lax.broadcasted_iota(jnp.int32,
                                                        x.shape, 1)
        a = jnp.where(col < cols, jnp.abs(x), 0.0)         # tail: no data
        amax_ref[...] = jnp.maximum(amax_ref[...],
                                    jnp.max(a, axis=1, keepdims=True))

    @pl.when(phase == 1)
    def _():
        _write_q(x, amax_ref[...], q_ref, s_ref)


def _dequant_kernel(q_ref, s_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)
    o_ref[...] = (q * s_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_blocked(x, *, interpret: bool = False):
    """x: (rows, C) float -> (int8 (rows, C), fp32 scales (rows, 1))."""
    rows, cols = x.shape
    br, bc = tiles(rows, cols)
    ni, nj = pl.cdiv(rows, br), pl.cdiv(cols, bc)
    out_shape = [jax.ShapeDtypeStruct((rows, cols), jnp.int8),
                 jax.ShapeDtypeStruct((rows, 1), jnp.float32)]
    if nj == 1:
        return pl.pallas_call(
            _quant_row_kernel,
            grid=(ni,),
            in_specs=[pl.BlockSpec((br, cols), lambda i: (i, 0))],
            out_specs=[pl.BlockSpec((br, cols), lambda i: (i, 0)),
                       pl.BlockSpec((br, 1), lambda i: (i, 0))],
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=interpret,
        )(x)
    return pl.pallas_call(
        functools.partial(_quant_tiled_kernel, cols=cols),
        grid=(ni, 2, nj),
        in_specs=[pl.BlockSpec((br, bc), lambda i, p, j: (i, j))],
        # q's block stays at column 0 through phase 0 (nothing written, no
        # write-back) and follows the column tiles in phase 1
        out_specs=[pl.BlockSpec((br, bc), lambda i, p, j: (i, j * p)),
                   pl.BlockSpec((br, 1), lambda i, p, j: (i, 0))],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((br, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("interpret", "out_dtype"))
def dequantize_blocked(q, s, *, out_dtype=jnp.float32,
                       interpret: bool = False):
    """q: (rows, C) int8, s: (rows, 1) fp32 -> (rows, C) out_dtype."""
    rows, cols = q.shape
    br, bc = tiles(rows, cols)
    return pl.pallas_call(
        _dequant_kernel,
        grid=(pl.cdiv(rows, br), pl.cdiv(cols, bc)),
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j)),
                  pl.BlockSpec((br, 1), lambda i, j: (i, 0))],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(q, s.astype(jnp.float32))
