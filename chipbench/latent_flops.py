"""Operations and bytes a latent-attention (MLA) MoE decoder's work needs,
from the configuration file's published keys alone (deepseek_v3 names).

Counted as chipbench/flops.py counts: a matmul of (m, k) by (k, n) is
2*m*k*n operations, an embedding lookup is free, norms and the router's
top-k are not counted.  Per layer and token:
  * MLA projections: q (d x H(dn+dr)), the latent and rope key
    (d x (r+dr)), the output (H dv x d);
  * decode, in the absorbed form: q_nope W_UK^T and the latent output
    through W_UV (H dn r + H r dv), then per context position, each head
    scores the whole row (r + dr) and sums the r latent values;
  * prefill, in the expanded form: per token K and V from the latent
    (r x H(dn+dv)), then per causal (query, key) pair H(dn+dr) for the
    score and H dv for the weighted sum;
  * the leading dense layers' gated MLP (3 d F), or the router (d E), the
    shared experts' gated MLP (3 d f n_shared) and the held routed
    experts at the share a token is expected to send here: k of the E
    published experts are chosen and n_routed_experts are held, so
    k * held / E experts' gated MLPs (3 d f each);
  * the head (d V) for each decoded token and a prompt's last position.

Keys: hidden_size, num_hidden_layers, first_k_dense_replace,
num_attention_heads, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
v_head_dim, intermediate_size, moe_intermediate_size, n_shared_experts,
num_experts_per_tok, n_routed_experts (held here),
n_routed_experts_published, vocab_size."""
from __future__ import annotations


def _dims(c: dict):
    return (c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"])


def mla_projection_params(c: dict) -> int:
    """Parameters of one layer's q, latent + rope key and output
    projections (the matmuls every token makes in either form)."""
    d, H, r, dn, dr, dv = _dims(c)
    return d * H * (dn + dr) + d * (r + dr) + H * dv * d


def ffn_params_per_token(c: dict, dense: bool) -> float:
    """Gated-MLP (and router) parameters a token multiplies in one layer;
    the routed experts at their expected share."""
    d = c["hidden_size"]
    if dense:
        return 3 * d * c["intermediate_size"]
    f = c["moe_intermediate_size"]
    share = (c["num_experts_per_tok"] * c["n_routed_experts"]
             / c["n_routed_experts_published"])
    return (d * c["n_routed_experts_published"]
            + 3 * d * f * (c["n_shared_experts"] + share))


def _token_matmuls(c: dict) -> float:
    """Operations of every layer's projections and FFN for one token."""
    L, k = c["num_hidden_layers"], c["first_k_dense_replace"]
    per = 2.0 * L * mla_projection_params(c)
    return per + 2.0 * (k * ffn_params_per_token(c, True)
                        + (L - k) * ffn_params_per_token(c, False))


def decode_attention_per_position(c: dict) -> int:
    """Absorbed attention operations per context position, all layers."""
    _, H, r, _, dr, _ = _dims(c)
    return c["num_hidden_layers"] * H * 2 * (2 * r + dr)


def decode(c: dict, ctx: int) -> float:
    """One decoded token that attends to `ctx` positions (itself too)."""
    d, H, r, dn, dr, dv = _dims(c)
    absorb = 2.0 * c["num_hidden_layers"] * H * r * (dn + dv)
    return (_token_matmuls(c) + absorb
            + decode_attention_per_position(c) * ctx
            + 2.0 * d * c["vocab_size"])


def prefill(c: dict, T: int) -> float:
    """A T-token prompt, expanded form, with the last position's logits."""
    d, H, r, dn, dr, dv = _dims(c)
    L = c["num_hidden_layers"]
    expand = 2.0 * L * r * H * (dn + dv)
    pairs = T * (T + 1) / 2
    return (T * (_token_matmuls(c) + expand)
            + 2.0 * L * H * (dn + dr + dv) * pairs
            + 2.0 * d * c["vocab_size"])


def latent_block_bytes(c: dict, block_size: int) -> int:
    """HBM bytes of one pool block's latent rows over every layer, as the
    work needs them: (kv_lora_rank + rope) bf16 values a token (not the
    lanes the rows are padded to)."""
    return (block_size * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * 2
            * c["num_hidden_layers"])
