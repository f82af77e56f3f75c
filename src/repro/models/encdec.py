"""Encoder-decoder transformer backbone (seamless-m4t-large-v2).

The speech/audio frontend is a STUB per the assignment: `input_specs()`
provides precomputed frame embeddings (B, enc_len, d_model).  The decoder is
a standard causal transformer with cross-attention; decode mode uses a self
KV cache plus a static cross-attention K/V cache computed at prefill.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.dist.sharding import constrain
from repro.models import cache as kvcache
from repro.models import layers as L
from repro.models.param import pdef, stack_defs

ENC_LEN_CAP = 4096  # frontend frames occupying the encoder (see DESIGN.md)


def enc_len_for(seq_len: int) -> int:
    return min(ENC_LEN_CAP, seq_len)


def _enc_block_defs(cfg):
    return {
        "ln1": L.norm_defs(cfg),
        "attn": L.attention_defs(cfg),
        "ln2": L.norm_defs(cfg),
        "mlp": L.mlp_defs(cfg),
    }


def _dec_block_defs(cfg):
    return {
        "ln1": L.norm_defs(cfg),
        "self_attn": L.attention_defs(cfg),
        "ln_x": L.norm_defs(cfg),
        "cross_attn": L.attention_defs(cfg),
        "ln2": L.norm_defs(cfg),
        "mlp": L.mlp_defs(cfg),
    }


def encdec_defs(cfg):
    return {
        "embed": L.embed_defs(cfg),
        "enc_layers": stack_defs(_enc_block_defs(cfg), cfg.enc_layers),
        "enc_norm": L.norm_defs(cfg),
        "dec_layers": stack_defs(_dec_block_defs(cfg), cfg.num_layers),
        "final_norm": L.norm_defs(cfg),
    }


def encode(params, cfg, frames):
    """frames: (B, Te, d) stub embeddings -> (B, Te, d) encoder states."""
    x = constrain(frames, ("batch", None, None))
    B, Te = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(Te, dtype=jnp.int32)[None], (B, Te))

    def body(x, lp):
        h = L.apply_norm(lp["ln1"], x, cfg.norm)
        a, _ = L.attention_apply(lp["attn"], cfg, h, positions,
                                 mode="train", causal=False)
        x = x + a
        h = L.apply_norm(lp["ln2"], x, cfg.norm)
        return x + L.mlp_apply(lp["mlp"], cfg, h), None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = lax.scan(body, x, params["enc_layers"])
    return L.apply_norm(params["enc_norm"], x, cfg.norm)


def _dec_block(lp, cfg, x, positions, enc_out, mode, cache):
    h = L.apply_norm(lp["ln1"], x, cfg.norm)
    a, self_cache = L.attention_apply(
        lp["self_attn"], cfg, h, positions, mode=mode,
        cache=cache["self"] if cache else None)
    x = x + a
    h = L.apply_norm(lp["ln_x"], x, cfg.norm)
    if mode == "decode":
        a, cross_cache = L.attention_apply(
            lp["cross_attn"], cfg, h, positions, mode="decode",
            cache=cache["cross"], is_cross=True)
    else:
        a, _ = L.attention_apply(lp["cross_attn"], cfg, h, positions,
                                 mode="train", kv_source=enc_out)
        # build the static cross K/V cache at prefill
        cross_cache = None
        if mode == "prefill":
            kk = jnp.einsum("bsd,dhk->bshk", enc_out, lp["cross_attn"]["wk"])
            vv = jnp.einsum("bsd,dhk->bshk", enc_out, lp["cross_attn"]["wv"])
            cross_cache = {"k": kk, "v": vv,
                           "len": jnp.full((x.shape[0],), enc_out.shape[1],
                                           jnp.int32)}
    x = x + a
    h = L.apply_norm(lp["ln2"], x, cfg.norm)
    x = x + L.mlp_apply(lp["mlp"], cfg, h)
    ncache = None
    if mode != "train":
        ncache = {"self": self_cache, "cross": cross_cache}
    return x, ncache


def encdec_cache_defs(cfg, batch: int, seq_len: int):
    el = enc_len_for(seq_len)
    per_layer = {
        "self": kvcache.attention_cache_defs(cfg, batch, seq_len),
        "cross": {
            "k": pdef((batch, el, cfg.num_kv_heads, cfg.head_dim),
                      ("batch", None, "kv_heads", "kv_head_dim"), init="zeros"),
            "v": pdef((batch, el, cfg.num_kv_heads, cfg.head_dim),
                      ("batch", None, "kv_heads", "kv_head_dim"), init="zeros"),
            "len": pdef((batch,), ("batch",), dtype=jnp.int32, init="zeros"),
        },
    }
    return stack_defs(per_layer, cfg.num_layers)


def encdec_apply(params, cfg, batch_inputs, *, mode="train", cache=None):
    """train/prefill: needs batch_inputs = {frames, tokens}.
    decode: {tokens (B,1)} + cache (encoder already folded into cross K/V)."""
    if mode == "decode":
        enc_out = None
    else:
        enc_out = encode(params, cfg, batch_inputs["frames"].astype(jnp.bfloat16))

    tokens = batch_inputs["tokens"]
    x = L.embed_apply(params["embed"], tokens)
    x = constrain(x, ("batch", None, None))
    B, T = x.shape[0], x.shape[1]

    if mode == "decode":
        positions = batch_inputs.get(
            "positions", cache["self"]["len"][0].reshape(B, 1))
    else:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))

    def body(x, xs):
        lp, lc = xs if mode == "decode" else (xs, None)
        return _dec_block(lp, cfg, x, positions, enc_out, mode, lc)

    if cfg.remat and mode == "train":
        body = jax.checkpoint(body, prevent_cse=False)

    xs = (params["dec_layers"], cache) if mode == "decode" \
        else params["dec_layers"]
    x, new_cache = lax.scan(body, x, xs)

    if mode == "prefill":
        x = x[:, -1:]  # serving needs only the last position's logits
    x = L.apply_norm(params["final_norm"], x, cfg.norm)
    logits = L.unembed_apply(params["embed"], x)
    logits = constrain(logits, ("batch", None, "vocab"))
    if mode == "train":
        return logits, 0.0
    return logits, new_cache
