"""Pure-jnp oracles: gather every table entry's block of the layer read,
then dense decode attention over the gathered view (the paged decode path
before the kernel); for a latent pool, the absorbed MLA attention over
the gathered rows."""
import jax

from repro.models.layers import (decode_attention, latent_attention,
                                 latent_gather, paged_gather_kv)


def paged_decode_attention_ref(q, kp, vp, layer, bt, lengths):
    with jax.named_scope("paged_gather"):
        k_seq, v_seq = paged_gather_kv(kp, vp, layer, bt)
    return decode_attention(q, k_seq.swapaxes(1, 2), v_seq.swapaxes(1, 2),
                            lengths)


def paged_latent_attention_ref(q, pool, layer, bt, lengths, *, scale, dv):
    rows = latent_gather(pool, layer, bt)
    return latent_attention(q[:, None], rows, (lengths - 1)[:, None],
                            scale=scale, dv=dv)[:, 0]
