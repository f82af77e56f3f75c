"""Public quant8 API mirroring core.compression's two scale layouts.

* `quantize` / `dequantize` -- BLOCKWISE wire format ((nblocks, block)
  int8 + per-block scales), for serialised transfer.
* `quantize_rowwise` / `dequantize_rowwise` -- per last-dim-channel
  scales; q keeps the input's shape (and therefore its sharding), the
  layout `federated.fl_aggregate_compressed` rides on the TPU hot path.

Both dispatch to the Pallas kernels (interpret mode off-TPU) unless
impl="ref" forces the jnp reference in core.compression.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.quant8.kernel import (BLOCK, dequantize_blocked,
                                         quantize_blocked)


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def quantize(x, *, block: int = BLOCK, impl: str = "auto"):
    """x any shape -> (q int8 (nblocks, block), scales (nblocks,))."""
    if impl == "ref":
        from repro.core.compression import quantize_blockwise
        return quantize_blockwise(x, block=block)
    flat = x.astype(jnp.float32).reshape(-1)
    pad = (-flat.shape[0]) % block
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    q, s = quantize_blocked(flat.reshape(-1, block),
                            interpret=_use_interpret())
    return q, s[:, 0]


def dequantize(q, scales, shape, *, out_dtype=jnp.float32,
               impl: str = "auto"):
    if impl == "ref":
        from repro.core.compression import dequantize_blockwise
        return dequantize_blockwise(q, scales, shape)
    flat = dequantize_blocked(q, scales.reshape(-1, 1),
                              out_dtype=out_dtype,
                              interpret=_use_interpret()).reshape(-1)
    return flat[:math.prod(shape)].reshape(shape)


def quantize_rowwise(x, *, impl: str = "auto"):
    """x: (..., C) -> (q int8 SAME shape, fp32 scales (..., 1)).

    The leading dims collapse to kernel rows; the result matches
    core.compression.quantize_rowwise exactly while the absmax + scale +
    round + cast run fused in VMEM."""
    if impl == "ref":
        from repro.core.compression import quantize_rowwise as ref
        return ref(x)
    C = x.shape[-1]
    x2 = x.astype(jnp.float32).reshape(-1, C)
    q, s = quantize_blocked(x2, interpret=_use_interpret())
    return q.reshape(x.shape), s.reshape(x.shape[:-1] + (1,))


def dequantize_rowwise(q, scale, *, out_dtype=jnp.float32,
                       impl: str = "auto"):
    """Inverse of quantize_rowwise: q (..., C) int8, scale (..., 1)."""
    if impl == "ref":
        from repro.core.compression import dequantize_rowwise as ref
        return ref(q, scale, out_dtype=out_dtype)
    C = q.shape[-1]
    out = dequantize_blocked(q.reshape(-1, C), scale.reshape(-1, 1),
                             out_dtype=out_dtype,
                             interpret=_use_interpret())
    return out.reshape(q.shape)
