"""Griffin-style hybrid (recurrentgemma-9b): RG-LRU recurrent blocks + local
sliding-window attention in a 2:1 pattern, each followed by a gated MLP.

38 layers = 12 super-blocks of [rec, rec, attn] (scanned) + a tail of
[rec, rec].  The RG-LRU diagonal recurrence reuses the same chunked linear
scan as the SSM module (and the `linrec` Pallas kernel on TPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.dist.sharding import constrain
from repro.models import cache as kvcache
from repro.models import layers as L
from repro.models.param import pdef, stack_defs
from repro.models.ssm import _causal_conv, _chunked_linear_scan

_C_RGLRU = 8.0


def rglru_defs(cfg):
    d, r = cfg.d_model, cfg.lru_width
    return {
        "w_x": pdef((d, r), ("embed", "lru_width"), fan_in_axes=(0,)),
        "w_y": pdef((d, r), ("embed", "lru_width"), fan_in_axes=(0,)),
        "conv_w": pdef((cfg.conv_width, r), (None, "lru_width")),
        "conv_b": pdef((r,), ("lru_width",), init="zeros"),
        "w_rgate": pdef((r, r), ("lru_width", None), fan_in_axes=(0,)),
        "b_rgate": pdef((r,), (None,), init="zeros"),
        "w_igate": pdef((r, r), ("lru_width", None), fan_in_axes=(0,)),
        "b_igate": pdef((r,), (None,), init="zeros"),
        "lam": pdef((r,), (None,), dtype=jnp.float32, init="scalar:-1.0"),
        "w_out": pdef((r, d), ("lru_width", "embed_tp"), fan_in_axes=(0,)),
    }


def rglru_apply(p, cfg, x, *, mode="train", cache=None):
    """Griffin recurrent block. x: (B,T,d) -> (out, new_cache)."""
    B, T, _ = x.shape
    w = cfg.conv_width
    xb = jnp.einsum("btd,dr->btr", x, p["w_x"])
    yb = jnp.einsum("btd,dr->btr", x, p["w_y"])
    xb = constrain(xb, ("batch", None, "lru_width"))

    if mode == "decode":
        win = jnp.concatenate([cache["conv"], xb], axis=1)   # (B,w,r)
        xc = jnp.einsum("bwr,wr->br", win.astype(jnp.float32),
                        p["conv_w"].astype(jnp.float32))
        xc = (xc + p["conv_b"].astype(jnp.float32)).astype(x.dtype)[:, None]
        conv_new = win[:, 1:]
    else:
        xc = _causal_conv(xb, p["conv_w"], p["conv_b"])
        conv_new = xb[:, -(w - 1):]

    rg = jax.nn.sigmoid(
        (jnp.einsum("btr,rs->bts", xc, p["w_rgate"])
         + p["b_rgate"]).astype(jnp.float32))
    ig = jax.nn.sigmoid(
        (jnp.einsum("btr,rs->bts", xc, p["w_igate"])
         + p["b_igate"]).astype(jnp.float32))
    log_a = -_C_RGLRU * jax.nn.softplus(p["lam"].astype(jnp.float32)) * rg
    a = jnp.exp(log_a)                                       # (B,T,r)
    gated_x = ig * xc.astype(jnp.float32)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.square(a), 1e-9)) * gated_x

    h0 = cache["h"] if mode == "decode" else jnp.zeros(
        (B, cfg.lru_width), jnp.float32)
    hs, hT = _chunked_linear_scan(a, b, h0, chunk=256)

    y = (hs.astype(x.dtype)) * jax.nn.gelu(yb)
    out = jnp.einsum("btr,rd->btd", y, p["w_out"])
    out = constrain(out, ("batch", None, None))

    new_cache = None
    if mode == "decode":
        new_cache = {"conv": conv_new, "h": hT, "len": cache["len"] + 1}
    elif mode == "prefill":
        new_cache = {"conv": conv_new, "h": hT,
                     "len": jnp.full((B,), T, jnp.int32)}
    return out, new_cache


def _residual_pair_defs(cfg, mixer: str):
    d = {"ln1": L.norm_defs(cfg), "ln2": L.norm_defs(cfg),
         "mlp": L.mlp_defs(cfg)}
    d["mix"] = rglru_defs(cfg) if mixer == "rec" else L.attention_defs(cfg)
    return d


def _pair_apply(p, cfg, x, positions, mixer, mode, cache):
    h = L.apply_norm(p["ln1"], x, cfg.norm)
    if mixer == "rec":
        a, new_cache = rglru_apply(p["mix"], cfg, h, mode=mode, cache=cache)
    else:
        a, new_cache = L.attention_apply(p["mix"], cfg, h, positions,
                                         mode=mode, cache=cache,
                                         window=cfg.window)
    x = x + a
    h = L.apply_norm(p["ln2"], x, cfg.norm)
    return x + L.mlp_apply(p["mlp"], cfg, h), new_cache


def _superblock_defs(cfg):
    return {
        "rec0": _residual_pair_defs(cfg, "rec"),
        "rec1": _residual_pair_defs(cfg, "rec"),
        "attn": _residual_pair_defs(cfg, "attn"),
    }


def hybrid_counts(cfg):
    n_super = cfg.num_layers // 3
    n_tail = cfg.num_layers - 3 * n_super  # leftover rec layers (0..2)
    return n_super, n_tail


def hybrid_lm_defs(cfg):
    n_super, n_tail = hybrid_counts(cfg)
    defs = {
        "embed": L.embed_defs(cfg),
        "super": stack_defs(_superblock_defs(cfg), n_super),
        "final_norm": L.norm_defs(cfg),
    }
    for i in range(n_tail):
        defs[f"tail{i}"] = _residual_pair_defs(cfg, "rec")
    return defs


def _rec_cache_defs(cfg, batch):
    return {
        "conv": pdef((batch, cfg.conv_width - 1, cfg.lru_width),
                     ("batch", None, "lru_width"), init="zeros"),
        "h": pdef((batch, cfg.lru_width), ("batch", "lru_width"),
                  dtype=jnp.float32, init="zeros"),
        "len": pdef((batch,), ("batch",), dtype=jnp.int32, init="zeros"),
    }


def hybrid_cache_defs(cfg, batch: int, seq_len: int):
    n_super, n_tail = hybrid_counts(cfg)
    per_super = {
        "rec0": _rec_cache_defs(cfg, batch),
        "rec1": _rec_cache_defs(cfg, batch),
        "attn": kvcache.attention_cache_defs(cfg, batch, seq_len),
    }
    defs = {"super": stack_defs(per_super, n_super)}
    for i in range(n_tail):
        defs[f"tail{i}"] = _rec_cache_defs(cfg, batch)
    return defs


def hybrid_lm_apply(params, cfg, batch_inputs, *, mode="train", cache=None):
    tokens = batch_inputs["tokens"]
    x = L.embed_apply(params["embed"], tokens)
    x = constrain(x, ("batch", None, None))
    B, T = x.shape[0], x.shape[1]
    n_super, n_tail = hybrid_counts(cfg)

    if mode == "decode":
        positions = batch_inputs.get(
            "positions", cache["super"]["rec0"]["len"][0].reshape(B, 1))
    else:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))

    def body(carry, xs):
        x = carry
        lp, lc = xs if mode == "decode" else (xs, None)
        ncache = {}
        x, ncache["rec0"] = _pair_apply(lp["rec0"], cfg, x, positions, "rec",
                                        mode, lc["rec0"] if lc else None)
        x, ncache["rec1"] = _pair_apply(lp["rec1"], cfg, x, positions, "rec",
                                        mode, lc["rec1"] if lc else None)
        x, ncache["attn"] = _pair_apply(lp["attn"], cfg, x, positions, "attn",
                                        mode, lc["attn"] if lc else None)
        return x, (ncache if mode != "train" else None)

    if cfg.remat and mode == "train":
        body = jax.checkpoint(body, prevent_cse=False)

    xs = (params["super"], cache["super"]) if mode == "decode" \
        else params["super"]
    x, super_cache = lax.scan(body, x, xs)

    new_cache = {"super": super_cache} if mode != "train" else None
    for i in range(n_tail):
        tc = cache[f"tail{i}"] if mode == "decode" else None
        x, nc = _pair_apply(params[f"tail{i}"], cfg, x, positions, "rec",
                            mode, tc)
        if mode != "train":
            new_cache[f"tail{i}"] = nc

    if mode == "prefill":
        x = x[:, -1:]  # serving needs only the last position's logits
    x = L.apply_norm(params["final_norm"], x, cfg.norm)
    logits = L.unembed_apply(params["embed"], x)
    logits = constrain(logits, ("batch", None, "vocab"))
    if mode == "train":
        return logits, 0.0
    return logits, new_cache
