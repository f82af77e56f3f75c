"""Public paged decode attention, over a K/V pool or a latent (MLA) pool:
the Pallas kernel where the shapes allow it on a TPU, the gather + dense
reference elsewhere."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import kernel, latent
from repro.kernels.paged_attention.ref import (paged_decode_attention_ref,
                                               paged_latent_attention_ref)

SUBLANES = 16     # bf16 sublane tile: a block's BS rows fill whole tiles
LANES = 128


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernel_fits(q, kp) -> bool:
    """The kernel's tiling needs lane-wide heads, whole bf16 tiles per
    block, and a bf16 pool."""
    D, BS = q.shape[-1], kp.shape[3]
    return (D % LANES == 0 and BS % SUBLANES == 0
            and kp.dtype == jnp.bfloat16)


def paged_decode_attention(q, kp, vp, layer, bt, lengths, *,
                           impl: str = "auto"):
    """q: (B, 1, H, D) over layer `layer` of the stacked (L, NB, Hkv, BS,
    D) pools kp/vp through the (B, nbmax) block tables bt; lengths (B,)
    counts the positions each slot attends (0 for a free slot, whose
    output is not read).

    impl="auto" runs the kernel on a TPU when `kernel_fits`, the
    reference otherwise; "pallas" (interpret mode off the TPU) and "ref"
    force one path, for tests.  The kernel is not partitioned: under a
    multi-device mesh it would need a shard_map."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() and kernel_fits(q, kp) else "ref"
    if impl == "ref":
        return paged_decode_attention_ref(q, kp, vp, layer, bt, lengths)
    return kernel.paged_decode_attention(q, kp, vp, layer, bt, lengths,
                                         interpret=not _on_tpu())


def latent_kernel_fits(q, pool) -> bool:
    """The latent kernel's tiling needs rows of whole lane tiles, whole
    bf16 tiles per block, and a bf16 pool."""
    W, BS = q.shape[-1], pool.shape[2]
    return (W % LANES == 0 and BS % SUBLANES == 0
            and pool.dtype == jnp.bfloat16)


def paged_latent_attention(q, pool, layer, bt, lengths, *, scale: float,
                           dv: int, impl: str = "auto"):
    """q: (B, H, W) absorbed queries over layer `layer` of the (L, NB, BS,
    W) latent pool through the (B, nbmax) block tables bt; lengths (B,)
    counts the positions each slot attends.  Returns (B, H, dv): the
    softmax(q . row * scale)-weighted sum of the rows' first dv lanes.

    impl as in `paged_decode_attention`."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() and latent_kernel_fits(q, pool) else "ref"
    if impl == "ref":
        return paged_latent_attention_ref(q, pool, layer, bt, lengths,
                                          scale=scale, dv=dv)
    return latent.paged_latent_attention(q, pool, layer, bt, lengths,
                                         scale=scale, dv=dv,
                                         interpret=not _on_tpu())
