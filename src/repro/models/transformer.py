"""Decoder-only LM (dense + MoE + VLM-stub), scan-over-layers.

Modes:
  train         -- full-sequence forward, returns (logits, aux)
  prefill       -- full-sequence forward, returns (logits, cache)
  decode        -- single-token step with a cache, returns (logits, cache)
  chunk_prefill -- a prompt chunk written into a cache, returns the last
                   valid position's (logits, cache)

Named scopes (`jax.named_scope`) mark each part's operations in the
compiled program's metadata, where a profiler trace finds them: embed,
attention (with paged_gather inside it on the paged paths; latent_write
and mla_absorb for latent attention), mlp / moe (router, routed_experts,
shared_experts) and head.  They are metadata only and leave the compiled
code as it was.

A decode or chunk_prefill batch that holds `block_tables` (B, nbmax) runs
the PAGED path: `cache` is the stacked block pool as `paged_cache_defs`
gives it -- the K/V pools {kp, vp} of (L, NB, Hkv, BS, D), or a
latent-attention (MLA) model's latent pool (L, NB, BS, W) -- and the
step returns the pool.  The tables and the batch's `positions` are step
inputs, shared by every layer.  The pool rides the layer scans as a
CARRY: each layer writes and reads its own slice in place, by its index,
so the pool is never sliced per layer or re-stacked, and a leading stack
of dense layers and the stack after it share it.  Without block tables
the contiguous cache (ServeLoop) rides the scan as xs, sliced and
re-stacked per layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.dist.sharding import constrain
from repro.models import cache as kvcache
from repro.models import layers as L
from repro.models.param import pdef, stack_defs, abstract_params


def block_defs(cfg, dense=False):
    d = {
        "ln1": L.norm_defs(cfg),
        "attn": L.mla_defs(cfg) if cfg.mla else L.attention_defs(cfg),
        "ln2": L.norm_defs(cfg),
    }
    if not dense and (cfg.family == "moe"
                      or (cfg.num_experts and cfg.family != "dense")):
        d["moe"] = L.moe_defs(cfg)
    else:
        d["mlp"] = L.mlp_defs(cfg)
    return d


def lm_defs(cfg):
    """`layers` is the uniform stack; a model with leading dense layers
    (cfg.first_dense_layers) keeps those in `dense_layers`, applied
    first."""
    k = cfg.first_dense_layers
    defs = {
        "embed": L.embed_defs(cfg),
        "layers": stack_defs(block_defs(cfg), cfg.num_layers - k),
        "final_norm": L.norm_defs(cfg),
    }
    if k:
        defs["dense_layers"] = stack_defs(block_defs(cfg, dense=True), k)
    return defs


def cache_defs(cfg, batch: int, seq_len: int, spec=None):
    """Decode-cache defs under a CacheSpec (default: cfg.cache_spec).
    The convention itself lives in models/cache.py.  MLA's latent cache
    takes no spec."""
    if cfg.mla:
        per_layer = kvcache.latent_cache_defs(cfg, batch, seq_len)
    else:
        per_layer = kvcache.attention_cache_defs(cfg, batch, seq_len, spec)
    return stack_defs(per_layer, cfg.num_layers)


def paged_cache_defs(cfg, num_blocks: int, block_size: int):
    """Block-table paged decode cache (see core/paging.py): one block
    pool per layer, a K/V pool or, for MLA, a latent pool, shared by all
    slots and stacked (L, NB, ...)."""
    if cfg.window:
        raise ValueError(
            f"{cfg.name}: sliding-window attention (window={cfg.window}) "
            "keeps a ring of the last positions; the paged pool does not")
    fn = (kvcache.paged_latent_cache_defs if cfg.mla
          else kvcache.paged_attention_cache_defs)
    return stack_defs(fn(cfg, num_blocks, block_size), cfg.num_layers)


def _block_apply(p, cfg, x, positions, mode, cache):
    with jax.named_scope("attention"):
        h = L.apply_norm(p["ln1"], x, cfg.norm, cfg.norm_eps)
        attend = L.mla_apply if cfg.mla else L.attention_apply
        a, new_cache = attend(p["attn"], cfg, h, positions, mode=mode,
                              cache=cache)
        x = x + a
    with jax.named_scope("moe" if "moe" in p else "mlp"):
        h = L.apply_norm(p["ln2"], x, cfg.norm, cfg.norm_eps)
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
    if mode in ("train", "prefill"):
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    elif "positions" in batch_inputs:
        # absolute positions of the step's tokens; -1 marks padding rows
        # (bucketed tail chunks, free decode slots) whose cache writes and
        # logits are dropped.
        positions = batch_inputs["positions"]
    else:
        # a contiguous decode cache's "len" is stacked (L, B); all layers
        # share the same length.
        positions = cache["len"][0].reshape(B, 1)
    bt = batch_inputs.get("block_tables")
    if bt is None and mode in ("decode", "chunk_prefill"):
        x, aux, new_cache = _contiguous_layers(params, cfg, x, positions,
                                               mode, cache)
    else:
        x, aux, new_cache = _layers(params, cfg, x, positions, mode, cache,
                                    bt)
    return _head(params, cfg, batch_inputs, x, mode, aux, new_cache)


def _layers(params, cfg, x, positions, mode, pool, bt):
    """The dense then the uniform stack.  On the paged path (block tables
    `bt` given) the stacked `pool` ({kp, vp} or {latent}, as the defs give
    it) is carried through both, each layer indexing its own slice; in
    train / prefill the per-layer caches (prefill's K/V, or None) are
    stacked as the scan returns them.  Returns (x, aux, new cache)."""
    paged = bt is not None
    if not paged:
        pool = None
    # a decode step has one position a slot: the scans close over them as
    # a (B,) vector, which XLA keeps in 128-lane tiles; a (B, 1) one would
    # be padded to (1, 128) tiles, 128 times its size
    flat = positions[:, 0] if mode == "decode" else positions
    aux, first, stacks = 0.0, 0, []
    for name in ("dense_layers", "layers"):
        if name not in params:
            continue

        def body(carry, xs):
            x, aux, pool = carry
            lp, i = xs
            lc = {**pool, "layer": i, "bt": bt} if paged else None
            pos = flat[:, None] if mode == "decode" else flat
            x, new, a = _block_apply(lp, cfg, x, pos, mode, lc)
            if paged:
                return (x, aux + a, new), None
            return (x, aux + a, pool), new

        if cfg.remat and mode == "train":
            body = jax.checkpoint(body, prevent_cse=False)
        n = jax.tree.leaves(params[name])[0].shape[0]
        (x, aux, pool), ys = lax.scan(
            body, (x, aux, pool),
            (params[name], first + jnp.arange(n, dtype=jnp.int32)))
        stacks.append(ys)
        first += n
    if paged:
        return x, aux, pool
    if len(stacks) == 1:
        return x, aux, stacks[0]
    return x, aux, jax.tree.map(lambda *a: jnp.concatenate(a), *stacks)


def _contiguous_layers(params, cfg, x, positions, mode, cache):
    """Decode / chunked prefill over the CONTIGUOUS spec'd cache {k, v,
    (scales,) len}, stacked (L, ...): each layer's slice rides the scan as
    xs and is re-stacked.  Returns (x, aux, new cache)."""
    if cfg.mla or "dense_layers" in params:
        raise ValueError(f"{cfg.name}: the contiguous cache covers a "
                         "uniform stack of K/V layers; this model decodes "
                         "through the paged pool (PagedServeLoop)")

    def body(carry, xs):
        x, aux = carry
        lp, lc = xs
        x, new_cache, a = _block_apply(lp, cfg, x, positions, mode, lc)
        return (x, aux + a), new_cache

    (x, aux), new_cache = lax.scan(body, (x, 0.0), (params["layers"], cache))
    return x, aux, new_cache


def _head(params, cfg, batch_inputs, x, mode, aux, new_cache):
    B = x.shape[0]
    with jax.named_scope("head"):
        if mode == "prefill":
            x = x[:, -1:]  # serving needs only the last position's logits
        elif mode == "chunk_prefill":
            # only the last VALID position's logits matter (tail chunks
            # are padded to a bucket length)
            li = batch_inputs["last_index"].reshape(B, 1, 1)
            x = jnp.take_along_axis(x, li, axis=1)
        x = L.apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
        logits = L.unembed_apply(params["embed"], x)
        logits = constrain(logits, ("batch", None, "vocab"))
    if mode == "train":
        return logits, aux
    return logits, new_cache
