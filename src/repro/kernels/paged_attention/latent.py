"""Pallas TPU kernel: single-token latent (MLA) decode attention over the
paged latent pool.

The pool is the model's stacked (L, NB, BS, W) array of latent rows, one
row [c_kv || k_pe || zero padding] per token and layer, and every query
head of a slot reads the same rows (MQA-shaped): the absorbed query
q_nope W_UK^T || q_pe is scored against the whole row, and the values are
the row's first `dv` lanes (c_kv).  So each block is read from HBM once
for all heads.  The layer index, the block tables and the lengths arrive
by scalar prefetch (SMEM); the grid is one step per slot, and a loop whose
trip count is the slot's length walks only the blocks it holds, CHUNK
blocks a stage into one of two VMEM buffers, the next stage's DMAs in
flight while the kernel computes on this one.  A slot of length 0 (a free
slot) reads nothing and writes zeros.

Numerics: q and the rows stay bf16 as stored; scores (q . row on the MXU)
accumulate in f32, and the online softmax's running max and sum and the
p.V accumulator are f32; the output is q's dtype.  Rows of a stage past
the slot's length hold whatever the buffer held before: their scores are
masked and their values zeroed before they meet p.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: The kernel's name: a profiler trace shows its operation under this
#: name, in whatever program runs it.
NAME = "paged_latent_attention"

#: Pool blocks per DMA stage (one DMA per block per stage).
CHUNK = 8

# the score of a position past the slot's length
_MASKED = -1e30


def _kernel(layer_ref, len_ref, bt_ref, q_ref, pool_hbm, o_ref, buf, sems,
            *, nbmax: int, bs: int, scale: float, dv: int):
    b = pl.program_id(0)
    layer = layer_ref[0]
    length = len_ref[b]
    nblk = (length + bs - 1) // bs
    nchunk = (nblk + CHUNK - 1) // CHUNK

    def copies(c, slot):
        """(block index, copy) for each block of chunk c."""
        out = []
        for i in range(CHUNK):
            j = c * CHUNK + i
            blk = bt_ref[b * nbmax + jnp.minimum(j, nbmax - 1)]
            out.append((j, pltpu.make_async_copy(
                pool_hbm.at[layer, blk], buf.at[slot, pl.ds(i * bs, bs)],
                sems.at[slot])))
        return out

    def start(c, slot):
        for j, cp in copies(c, slot):
            @pl.when(j < nblk)
            def _():
                cp.start()

    def wait(c, slot):
        for j, cp in copies(c, slot):
            @pl.when(j < nblk)
            def _():
                cp.wait()

    q = q_ref[...]                                           # (H, W)
    n = CHUNK * bs

    @pl.when(nchunk > 0)
    def _():
        start(0, 0)

    def chunk_step(c, carry):
        m, l, acc = carry
        slot = c % 2

        @pl.when(c + 1 < nchunk)
        def _():
            start(c + 1, 1 - slot)

        wait(c, slot)
        rows = buf[slot]                                     # (n, W)
        s = lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        pos = c * n + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, _MASKED)              # (H, n)
        vpos = c * n + lax.broadcasted_iota(jnp.int32, (n, 1), 0)
        v = jnp.where(vpos < length, rows[:, :dv].astype(jnp.float32), 0.0)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc + jnp.dot(p, v, preferred_element_type=jnp.float32)
        return m_new, l, acc

    H = q.shape[0]
    carry = (jnp.full((H, 1), -jnp.inf, jnp.float32),
             jnp.zeros((H, 1), jnp.float32),
             jnp.zeros((H, dv), jnp.float32))
    _, l, acc = lax.fori_loop(0, nchunk, chunk_step, carry)
    # a slot of length 0 has l == acc == 0 and writes zeros
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "dv", "interpret"))
def paged_latent_attention(q, pool, layer, bt, lengths, *, scale: float,
                           dv: int, interpret: bool = False):
    """q: (B, H, W) absorbed queries; pool: (L, NB, BS, W) latent rows;
    layer: int32 scalar, the pool's layer read; bt: (B, nbmax) int32 block
    tables; lengths: (B,) int32 positions attended (0 for a free slot).
    Returns (B, H, dv) in q's dtype."""
    B, H, W = q.shape
    BS = pool.shape[2]
    nbmax = bt.shape[1]
    row = lambda d: pl.BlockSpec((None, H, d), lambda b, *_: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, nbmax=nbmax, bs=BS, scale=scale, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[row(W), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row(dv),
            scratch_shapes=[pltpu.VMEM((2, CHUNK * BS, W), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((B, H, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=NAME,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lengths.astype(jnp.int32),
      bt.reshape(-1).astype(jnp.int32), q, pool)
