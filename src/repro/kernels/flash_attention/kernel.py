"""Pallas TPU kernel: causal / sliding-window GQA flash attention (fwd).

TPU adaptation of the FlashAttention blocking:
  grid = (batch, q_heads, nq, nkv) with the kv dimension SEQUENTIAL
  ('arbitrary'); q/k/v stream through VMEM in (BQ, D) / (BK, D) tiles, the
  online-softmax stats (m, l) and the (BQ, D) accumulator live in VMEM
  scratch across kv steps.  GQA is an index_map: q head h reads kv head
  h // group.  Causal and sliding-window blocks that are fully masked are
  SKIPPED via pl.when (true compute skipping, unlike a masked XLA einsum --
  this is the kernel's roofline win for the prefill cells, ~halving the
  attention FLOPs at 32k).

Block sizes default to (BQ, BK) = (512, 512): VMEM per step is q 512x128 +
k/v 2x512x128 bf16 (~0.4 MB) + fp32 acc 512x128 (0.25 MB), comfortably
inside ~16 MB VMEM with double buffering; MXU tiles are 128-aligned.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 512
DEFAULT_BK = 512
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  causal: bool, window: int, bq: int, bk: int, nkv: int,
                  scale: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = iq * bq
    k_lo = ik * bk
    # block-level skip: strictly-above-diagonal (causal) or out-of-window
    live = jnp.bool_(True)
    if causal:
        live = jnp.logical_and(live, k_lo <= q_lo + bq - 1)
    if window:
        live = jnp.logical_and(live, k_lo + bk - 1 > q_lo - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)           # (BQ, D)
        k = k_ref[0, 0].astype(jnp.float32)           # (BK, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (BQ, BK)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                            # (BQ, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new

    @pl.when(ik == nkv - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention_bhtd(q, k, v, *, causal: bool = True, window: int = 0,
                         bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                         interpret: bool = False):
    """q: (B, H, T, D); k/v: (B, Hkv, S, D).  Returns (B, H, T, D)."""
    B, H, T, D = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    G = H // Hkv
    bq = min(bq, T)
    bk = min(bk, S)
    assert T % bq == 0 and S % bk == 0, (T, bq, S, bk)
    nq, nkv = T // bq, S // bk
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(_flash_kernel, causal=causal, window=window,
                               bq=bq, bk=bk, nkv=nkv, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
