"""Pallas TPU kernel: single-token decode attention over the paged KV pool.

Each slot attends to the blocks its table names, read straight from
layer `layer` of the stacked (L, NB, Hkv, BS, D) pools in HBM -- only
the `ceil(len / BS)` blocks the slot holds, never the whole table.  The
layer, the block tables and the lengths arrive by scalar prefetch
(SMEM); the grid is one step per slot, and inside it a loop whose trip
count is that slot's length walks its blocks.

Reads: a pool block is head-major, (Hkv, BS, D), and each head's BS rows
are whole bf16 tiles, so the block is stored unpadded and one DMA moves
its every KV head as stored.  Blocks travel CHUNK at a time into one of
two VMEM buffers: while the kernel computes on one chunk, the next
chunk's DMAs are in flight.  Only blocks the slot holds are fetched, and
only those are computed on; a slot of length 0 (a free slot) reads
nothing and writes zeros.

Numerics: q and K/V stay in their stored dtype in HBM; products, the
online softmax's running max and sum, and the p.V accumulator are f32
in VMEM; the output is q's dtype.  Scores are formed per KV head on the
VPU (an elementwise product reduced over the lanes of D): the G query
rows of head h against the block's BS rows of head h, which fits any
head grouping G = H / Hkv.
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


def _kernel(layer_ref, len_ref, bt_ref, q_ref, kp_hbm, vp_hbm, o_ref, kbuf,
            vbuf, sems, *, nbmax: int, scale: float):
    b = pl.program_id(0)
    layer = layer_ref[0]
    bs = kbuf.shape[3]
    length = len_ref[b]
    nblk = (length + bs - 1) // bs
    nchunk = (nblk + CHUNK - 1) // CHUNK

    def copies(c, buf):
        """(block index, K copy, V copy) for each block of chunk c."""
        out = []
        for i in range(CHUNK):
            j = c * CHUNK + i
            blk = bt_ref[b * nbmax + jnp.minimum(j, nbmax - 1)]
            out.append((j,
                        pltpu.make_async_copy(kp_hbm.at[layer, blk],
                                              kbuf.at[buf, i],
                                              sems.at[0, buf]),
                        pltpu.make_async_copy(vp_hbm.at[layer, blk],
                                              vbuf.at[buf, i],
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

    q = q_ref[...].astype(jnp.float32)                       # (Hkv,G,1,D)

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
            k = kbuf[buf, i].astype(jnp.float32)[:, None]    # (Hkv,1,BS,D)
            v = vbuf[buf, i].astype(jnp.float32)[:, None]
            s = jnp.sum(k * q, axis=-1, keepdims=True) * scale
            pos = (c * CHUNK + i) * bs + lax.broadcasted_iota(
                jnp.int32, s.shape, 2)                       # (Hkv,G,BS,1)
            s = jnp.where(pos < length, s, _MASKED)
            m_new = jnp.maximum(m, jnp.max(s, axis=2, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + jnp.sum(p, axis=2, keepdims=True)
            acc = alpha * acc + jnp.sum(p * v, axis=2, keepdims=True)
            return m_new, l, acc

        n = jnp.minimum(CHUNK, nblk - c * CHUNK)
        return lax.fori_loop(0, n, block_step, carry)

    Hkv, G, _, D = q.shape
    carry = (jnp.full((Hkv, G, 1, 1), -jnp.inf, jnp.float32),
             jnp.zeros((Hkv, G, 1, 1), jnp.float32),
             jnp.zeros((Hkv, G, 1, D), jnp.float32))
    _, l, acc = lax.fori_loop(0, nchunk, chunk_step, carry)
    # a slot of length 0 has l == acc == 0 and writes zeros
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, kp, vp, layer, bt, lengths, *,
                           interpret: bool = False):
    """q: (B, 1, H, D); kp/vp: (L, NB, Hkv, BS, D) stacked pools; layer:
    int32 scalar, the pools' layer read; bt: (B, nbmax) int32 block
    tables; lengths: (B,) int32 positions attended (0 for a free slot).
    Returns (B, 1, H, D) in q's dtype."""
    B, _, H, D = q.shape
    _, _, Hkv, BS, _ = kp.shape
    G = H // Hkv
    nbmax = bt.shape[1]
    # query head h * G + g reads KV head h (decode_attention's grouping)
    qg = q.reshape(B, Hkv, G, 1, D)
    row = pl.BlockSpec((None, Hkv, G, 1, D), lambda b, *_: (b, 0, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, nbmax=nbmax, scale=1.0 / math.sqrt(D)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[row, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row,
            scratch_shapes=[pltpu.VMEM((2, CHUNK, Hkv, BS, D), kp.dtype),
                            pltpu.VMEM((2, CHUNK, Hkv, BS, D), vp.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, 1, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=NAME,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lengths.astype(jnp.int32),
      bt.reshape(-1).astype(jnp.int32), qg, kp, vp)
    return out.reshape(B, 1, H, D)
