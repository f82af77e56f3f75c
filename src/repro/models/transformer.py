"""Decoder-only LM (dense + MoE + VLM-stub), scan-over-layers, 3 modes.

Modes:
  train   -- full-sequence forward, returns (logits, aux)
  prefill -- full-sequence forward, returns (logits, cache)
  decode  -- single-token step with KV cache, returns (logits, cache)

Named scopes (`jax.named_scope`) mark each part's operations in the
compiled program's metadata, where a profiler trace finds them: embed,
attention (with paged_gather inside it on the paged paths), mlp / moe and
head.  They are metadata only and leave the compiled code as it was.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.dist.sharding import constrain
from repro.models import layers as L
from repro.models.param import pdef, stack_defs, abstract_params


def block_defs(cfg):
    d = {
        "ln1": L.norm_defs(cfg),
        "attn": L.attention_defs(cfg),
        "ln2": L.norm_defs(cfg),
    }
    if cfg.family == "moe" or (cfg.num_experts and cfg.family != "dense"):
        d["moe"] = L.moe_defs(cfg)
    else:
        d["mlp"] = L.mlp_defs(cfg)
    return d


def lm_defs(cfg):
    return {
        "embed": L.embed_defs(cfg),
        "layers": stack_defs(block_defs(cfg), cfg.num_layers),
        "final_norm": L.norm_defs(cfg),
    }


def cache_defs(cfg, batch: int, seq_len: int, spec=None):
    """Decode-cache defs under a CacheSpec (default: cfg.cache_spec).
    The convention itself lives in models/cache.py."""
    per_layer = L.attention_cache_defs(cfg, batch, seq_len, spec)
    return stack_defs(per_layer, cfg.num_layers)


def paged_cache_defs(cfg, batch: int, num_blocks: int, block_size: int,
                     max_blocks_per_seq: int):
    """Block-table paged decode cache (see core/paging.py): one KV block
    pool per layer, shared by all slots, plus per-slot tables/lengths."""
    per_layer = L.paged_attention_cache_defs(
        cfg, batch, num_blocks, block_size, max_blocks_per_seq)
    return stack_defs(per_layer, cfg.num_layers)


def _block_apply(p, cfg, x, positions, mode, cache):
    with jax.named_scope("attention"):
        h = L.apply_norm(p["ln1"], x, cfg.norm)
        a, new_cache = L.attention_apply(p["attn"], cfg, h, positions,
                                         mode=mode, cache=cache)
        x = x + a
    with jax.named_scope("moe" if "moe" in p else "mlp"):
        h = L.apply_norm(p["ln2"], x, cfg.norm)
        if "moe" in p:
            m, aux = L.moe_apply(p["moe"], cfg, h)
        else:
            m, aux = L.mlp_apply(p["mlp"], cfg, h), 0.0
        return x + m, new_cache, aux


def _embed_inputs(params, cfg, batch_inputs):
    """tokens (+ optional stub modality embeddings occupying a prefix)."""
    tokens = batch_inputs["tokens"]
    x = L.embed_apply(params["embed"], tokens)
    if cfg.frontend == "vision_stub" and "patch_embeds" in batch_inputs:
        pe = batch_inputs["patch_embeds"].astype(x.dtype)
        P = pe.shape[1]
        x = jnp.concatenate([pe, x[:, P:]], axis=1)
    return constrain(x, ("batch", None, None))


def lm_apply(params, cfg, batch_inputs, *, mode="train", cache=None):
    with jax.named_scope("embed"):
        x = _embed_inputs(params, cfg, batch_inputs)
    B, T = x.shape[0], x.shape[1]
    if mode == "decode":
        # cache["len"] is stacked (L, B); all layers share the same length.
        positions = batch_inputs.get("positions", cache["len"][0].reshape(B, 1))
    elif mode == "chunk_prefill":
        # absolute positions of this chunk's tokens; -1 marks padding rows
        # (bucketed tail chunks) whose cache writes and logits are dropped.
        positions = batch_inputs["positions"]
    else:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    bt = batch_inputs.get("block_tables")  # (B, nbmax), chunk_prefill only

    def body(carry, xs):
        x, aux = carry
        if mode in ("decode", "chunk_prefill"):
            lp, lc = xs
        else:
            lp, lc = xs, None
        if mode == "chunk_prefill" and bt is not None:
            lc = {**lc, "bt": bt}
        x, new_cache, a = _block_apply(lp, cfg, x, positions, mode, lc)
        if mode == "chunk_prefill" and bt is not None:
            # paged: bt rides in batch_inputs, only the pool is carried;
            # the CONTIGUOUS chunked path (no block tables) carries the
            # whole spec'd cache {k, v, (scales,) len} like decode does
            new_cache = {k: new_cache[k] for k in ("kp", "vp")}
        return (x, aux + a), new_cache

    if cfg.remat and mode == "train":
        body = jax.checkpoint(body, prevent_cse=False)

    if mode in ("decode", "chunk_prefill"):
        # cache leaves are stacked (L, ...): per-layer slices ride the scan.
        (x, aux), new_cache = lax.scan(body, (x, 0.0),
                                       (params["layers"], cache))
    else:
        (x, aux), new_cache = lax.scan(body, (x, 0.0), params["layers"])

    with jax.named_scope("head"):
        if mode == "prefill":
            x = x[:, -1:]  # serving needs only the last position's logits
        elif mode == "chunk_prefill":
            # only the last VALID position's logits matter (tail chunks
            # are padded to a bucket length)
            li = batch_inputs["last_index"].reshape(B, 1, 1)
            x = jnp.take_along_axis(x, li, axis=1)
        x = L.apply_norm(params["final_norm"], x, cfg.norm)
        logits = L.unembed_apply(params["embed"], x)
        logits = constrain(logits, ("batch", None, "vocab"))
    if mode == "train":
        return logits, aux
    return logits, new_cache
