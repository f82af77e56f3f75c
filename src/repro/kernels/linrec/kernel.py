"""Pallas TPU kernel: blocked diagonal linear recurrence
    h_t = a_t * h_{t-1} + b_t        (h, a, b: (..., D) elementwise)

Shared by the mamba selective scan (D = d_inner*N flattened) and the RG-LRU
(D = lru_width).  TPU adaptation of the fused CUDA selective-scan: the
sequence is streamed through VMEM in (BT, BD) tiles with the carry h held
in VMEM scratch across T tiles, so the (B, T, D) state trajectory never
round-trips HBM more than once.  grid = (B, D//BD, T//BT) with T
SEQUENTIAL; the in-tile recurrence is a log-depth blelloch-style composite
(associative (a,b) combine) to keep the VPU busy instead of a scalar loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BT = 256
DEFAULT_BD = 512


def _linrec_kernel(a_ref, b_ref, o_ref, h_scr, *, bt: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[0].astype(jnp.float32)     # (BT, BD)
    b = b_ref[0].astype(jnp.float32)

    # in-tile prefix composition: after the loop, A[t] = prod a[..t],
    # B[t] = sum_j (prod_{j<i<=t} a[i]) b[j]  -- log2(BT) doubling steps.
    A, Bc = a, b
    shift = 1
    while shift < bt:
        A_prev = jnp.concatenate(
            [jnp.ones((shift, A.shape[1]), A.dtype), A[:-shift]], axis=0)
        B_prev = jnp.concatenate(
            [jnp.zeros((shift, Bc.shape[1]), Bc.dtype), Bc[:-shift]], axis=0)
        Bc = Bc + A * B_prev
        A = A * A_prev
        shift *= 2

    h0 = h_scr[...]                      # (1, BD)
    hs = A * h0 + Bc                     # (BT, BD)
    o_ref[0] = hs.astype(o_ref.dtype)
    h_scr[...] = hs[-1:]


@functools.partial(jax.jit, static_argnames=("bt", "bd", "interpret"))
def linrec_btd(a, b, *, bt: int = DEFAULT_BT, bd: int = DEFAULT_BD,
               interpret: bool = False):
    """a, b: (B, T, D) -> hs (B, T, D) with h_t = a_t h_{t-1} + b_t, h_0=b_0."""
    B, T, D = a.shape
    bt = min(bt, T)
    bd = min(bd, D)
    assert T % bt == 0 and D % bd == 0, (T, bt, D, bd)

    kernel = functools.partial(_linrec_kernel, bt=bt)
    return pl.pallas_call(
        kernel,
        grid=(B, D // bd, T // bt),
        in_specs=[
            pl.BlockSpec((1, bt, bd), lambda ib, jd, it: (ib, it, jd)),
            pl.BlockSpec((1, bt, bd), lambda ib, jd, it: (ib, it, jd)),
        ],
        out_specs=pl.BlockSpec((1, bt, bd), lambda ib, jd, it: (ib, it, jd)),
        out_shape=jax.ShapeDtypeStruct((B, T, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
