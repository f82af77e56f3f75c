"""Operations and bytes the work needs, from shapes alone.

Counted as the algorithm needs them, whatever the program does: a matmul
of (m, k) by (k, n) is 2*m*k*n operations; causal attention of a query at
position p reads p + 1 keys (4 * heads * head_dim operations each, for
the scores and the weighted sum); an embedding lookup is free.  The
program may do more (the paged step computes every slot of the batch and
gathers the whole block pool); that surplus is what a utilisation below
the peak shows."""
from __future__ import annotations

import math


def decoder_layer_params(c: dict) -> int:
    """Matmul parameters of one dense decoder layer (QKV, output, gated
    MLP; norms and biases are not matmuls)."""
    d, f = c["hidden_size"], c["intermediate_size"]
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    Dh = d // H
    return d * Dh * (H + 2 * Hkv) + H * Dh * d + 3 * d * f


def _attn(c: dict) -> int:
    return 4 * c["num_hidden_layers"] * c["hidden_size"]


def decoder_prefill(c: dict, T: int) -> float:
    """A T-token prompt, with the logits of its last position only."""
    L = c["num_hidden_layers"]
    return (2.0 * T * L * decoder_layer_params(c)
            + _attn(c) * T * (T + 1) / 2
            + 2.0 * c["hidden_size"] * c["vocab_size"])


def decoder_decode(c: dict, ctx: int) -> float:
    """One decoded token that attends to `ctx` positions (itself too)."""
    L = c["num_hidden_layers"]
    return (2.0 * L * decoder_layer_params(c) + _attn(c) * ctx
            + 2.0 * c["hidden_size"] * c["vocab_size"])


def cnn_forward(c: dict) -> float:
    """One image through the 3x3 'SAME' conv + 2x2 max-pool stack and the
    dense head (pooling and ReLU are not counted)."""
    hw, cin = c["image_size"], c["image_channels"]
    total = 0.0
    for cout in c["conv_channels"]:
        total += 2.0 * hw * hw * 9 * cin * cout
        hw, cin = hw // 2, cout
    return total + 2.0 * hw * hw * cin * c["num_classes"]


def cnn_params(c: dict) -> dict:
    """{leaf: shape} of the CNN, in the program's leaf names."""
    cin, hw = c["image_channels"], c["image_size"]
    out = {}
    for i, cout in enumerate(c["conv_channels"]):
        out[f"conv{i}_w"] = (3, 3, cin, cout)
        out[f"conv{i}_b"] = (cout,)
        cin, hw = cout, hw // 2
    out["fc_w"] = (hw * hw * cin, c["num_classes"])
    out["fc_b"] = (c["num_classes"],)
    return out


def quant8_bytes(shape) -> int:
    """HBM bytes a rowwise int8 quantise of an fp32 array needs: every
    element read (4 B) and written as int8 (1 B), and one fp32 scale per
    row of the last axis."""
    n = math.prod(shape)
    return 5 * n + 4 * (n // shape[-1])
