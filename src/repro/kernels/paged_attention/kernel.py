"""Pallas TPU kernel: single-token decode attention over the paged KV pool.

Each slot attends to the blocks its table names, read straight from the
(NB, BS, Hkv, D) pool in HBM -- only the `ceil(len / BS)` blocks the
slot holds, never the whole table.  The block tables and lengths arrive
by scalar prefetch (SMEM); the grid is one step per slot, and inside it
a loop whose trip count is that slot's length walks its blocks.

Reads: a pool block's BS x Hkv x D values are contiguous, so one DMA
carries a block's every KV head.  On the TPU the head axis is stored
padded to the sublane tile (`stored_heads`: 20 heads take 24 rows) and
Mosaic slices only whole tiles, so the DMA moves the block as stored,
padding rows and all; those rows meet zero queries, every step of the
arithmetic keeps rows apart, and they are cut from the output.  Blocks
travel CHUNK at a time into one of two VMEM buffers: while the kernel
computes on one chunk, the next chunk's DMAs are in flight.  Only blocks
the slot holds are fetched, and only those are computed on; a slot of
length 0 (a free slot) reads nothing and writes zeros.

Numerics: q and K/V stay in their stored dtype in HBM; products, the
online softmax's running max and sum, and the p.V accumulator are f32
in VMEM; the output is q's dtype.  Scores are formed on the VPU (an
elementwise product reduced over the lanes of D), which fits any head
grouping G = H / Hkv without moving the pool's head axis.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: The kernel's name: a profiler trace shows its operation under this
#: name, in whatever program runs it.
NAME = "paged_decode_attention"

#: Pool blocks per DMA stage (one DMA per block per stage).
CHUNK = 8

# the score of a position past the slot's length (decode_attention's)
_MASKED = -1e30


def stored_heads(n: int) -> int:
    """Rows a bf16 array's second-minor axis of size n takes in HBM: Mosaic
    tiles it by 8 rows from 8 up, else by the next power of two (at
    least 2, two bf16 rows share a sublane)."""
    tile = 8 if n >= 8 else max(2, 1 << (n - 1).bit_length())
    return -(-n // tile) * tile


def _kernel(len_ref, bt_ref, q_ref, kp_hbm, vp_hbm, o_ref, kbuf, vbuf,
            sems, *, nbmax: int, scale: float):
    b = pl.program_id(0)
    bs, rows = kbuf.shape[2], kbuf.shape[3]
    length = len_ref[b]
    nblk = (length + bs - 1) // bs
    nchunk = (nblk + CHUNK - 1) // CHUNK

    def copies(c, buf):
        """(block index, K copy, V copy) for each block of chunk c."""
        out = []
        for i in range(CHUNK):
            j = c * CHUNK + i
            blk = bt_ref[b * nbmax + jnp.minimum(j, nbmax - 1)]
            src = (blk, slice(None), pl.ds(0, rows))   # the block as stored
            out.append((j,
                        pltpu.make_async_copy(kp_hbm.at[src], kbuf.at[buf, i],
                                              sems.at[0, buf]),
                        pltpu.make_async_copy(vp_hbm.at[src], vbuf.at[buf, i],
                                              sems.at[1, buf])))
        return out

    def start(c, buf):
        for j, ck, cv in copies(c, buf):
            @pl.when(j < nblk)
            def _():
                ck.start()
                cv.start()

    def wait(c, buf):
        for j, ck, cv in copies(c, buf):
            @pl.when(j < nblk)
            def _():
                ck.wait()
                cv.wait()

    q = q_ref[...].astype(jnp.float32)                       # (G, R, D)

    @pl.when(nchunk > 0)
    def _():
        start(0, 0)

    def chunk_step(c, carry):
        buf = c % 2

        @pl.when(c + 1 < nchunk)
        def _():
            start(c + 1, 1 - buf)

        wait(c, buf)

        def block_step(i, carry):
            m, l, acc = carry
            k = kbuf[buf, i].astype(jnp.float32)[:, None]    # (BS,1,R,D)
            v = vbuf[buf, i].astype(jnp.float32)[:, None]
            s = jnp.sum(k * q[None], axis=-1, keepdims=True) * scale
            pos = (c * CHUNK + i) * bs + lax.broadcasted_iota(
                jnp.int32, s.shape, 0)                       # (BS,G,R,1)
            s = jnp.where(pos < length, s, _MASKED)
            m_new = jnp.maximum(m, jnp.max(s, axis=0))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[None])
            l = alpha * l + jnp.sum(p, axis=0)
            acc = alpha * acc + jnp.sum(p * v, axis=0)
            return m_new, l, acc

        n = jnp.minimum(CHUNK, nblk - c * CHUNK)
        return lax.fori_loop(0, n, block_step, carry)

    G, R, D = q.shape
    carry = (jnp.full((G, R, 1), -jnp.inf, jnp.float32),
             jnp.zeros((G, R, 1), jnp.float32),
             jnp.zeros((G, R, D), jnp.float32))
    _, l, acc = lax.fori_loop(0, nchunk, chunk_step, carry)
    # a slot of length 0 has l == acc == 0 and writes zeros
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, kp, vp, bt, lengths, *,
                           interpret: bool = False):
    """q: (B, 1, H, D); kp/vp: (NB, BS, Hkv, D) pool; bt: (B, nbmax)
    int32 block tables; lengths: (B,) int32 positions attended (0 for a
    free slot).  Returns (B, 1, H, D) in q's dtype."""
    B, _, H, D = q.shape
    _, BS, Hkv, _ = kp.shape
    G = H // Hkv
    nbmax = bt.shape[1]
    R = Hkv if interpret else stored_heads(Hkv)   # no padding off the TPU
    # query head h * G + g reads KV head h (decode_attention's grouping);
    # the padding rows R - Hkv get zero queries
    qg = q.reshape(B, Hkv, G, D).transpose(0, 2, 1, 3)       # (B,G,Hkv,D)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, R - Hkv), (0, 0)))
    row = pl.BlockSpec((None, G, R, D), lambda b, *_: (b, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, nbmax=nbmax, scale=1.0 / math.sqrt(D)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[row, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row,
            scratch_shapes=[pltpu.VMEM((2, CHUNK, BS, R, D), kp.dtype),
                            pltpu.VMEM((2, CHUNK, BS, R, D), vp.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct((B, G, R, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=NAME,
    )(lengths.astype(jnp.int32), bt.reshape(-1).astype(jnp.int32), qg,
      kp, vp)
    return out[:, :, :Hkv].transpose(0, 2, 1, 3).reshape(B, 1, H, D)
