"""Pure-jnp oracle: gather every table entry's block, then dense decode
attention over the gathered view (the paged decode path before the
kernel)."""
import jax

from repro.models.layers import decode_attention, paged_gather_kv


def paged_decode_attention_ref(q, kp, vp, bt, lengths):
    with jax.named_scope("paged_gather"):
        k_seq, v_seq = paged_gather_kv(kp, vp, bt)
    return decode_attention(q, k_seq, v_seq, lengths)
