"""Decode-path correctness: prefill + single-token decode must reproduce the
teacher-forced logits at the next position (per model family).

This is the strongest serving-correctness test we can run on CPU: it
exercises KV caches, ring buffers (SWA), SSM/RG-LRU state carry, and the
cross-attention cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model

# one representative per decode code path
FAMILIES = [
    "granite-20b",            # dense MQA, full attention
    "chatglm3-6b",            # GQA + partial rope + qkv bias
    "qwen3-moe-235b-a22b",    # MoE decode
    "falcon-mamba-7b",        # SSM state
    "recurrentgemma-9b",      # hybrid RG-LRU + local attention ring
    "seamless-m4t-large-v2",  # enc-dec with cross-attention cache
]


def _batch(model, T, B=2, seed=0):
    rng = np.random.default_rng(seed)
    cfg = model.cfg
    b = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)),
                               jnp.int32)}
    if cfg.frontend == "vision_stub":
        b["patch_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.frontend_len, cfg.d_model)), jnp.bfloat16)
    if cfg.is_encdec:
        b["frames"] = jnp.asarray(rng.normal(size=(B, T, cfg.d_model)),
                                  jnp.bfloat16)
    return b


@pytest.mark.parametrize("arch", FAMILIES)
def test_prefill_then_decode_matches_teacher_forcing(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.key(1))
    T, B = 16, 2
    full = _batch(model, T + 1, B)

    # teacher-forced reference: logits at position T-? -> prediction for
    # position T given tokens[:T]; compare logits AT the last position.
    tf_in = {k: (v[:, : T] if k in ("tokens",) else v)
             for k, v in full.items()}
    # run T+1 tokens through train mode, take logits at index T
    ref_logits, _ = model.apply(params, full, mode="train")
    ref_last = np.asarray(ref_logits[:, T, :], np.float32)

    # prefill on T tokens, then decode token T
    _, cache = model.apply(params, tf_in, mode="prefill")
    dec_in = {"tokens": full["tokens"][:, T: T + 1],
              "positions": jnp.full((B, 1), T, jnp.int32)}
    dec_logits, _ = model.apply(params, dec_in, mode="decode", cache=cache)
    got = np.asarray(dec_logits[:, 0, :], np.float32)

    np.testing.assert_allclose(got, ref_last, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("arch,dense", [("granite-20b", 0),
                                        ("chatglm3-6b", 0),
                                        ("qwen3-moe-235b-a22b", 1)],
                         ids=["granite-20b", "chatglm3-6b",
                              "qwen3-moe-235b-a22b-dense1"])
def test_paged_chunk_prefill_then_decode_matches_teacher_forcing(arch, dense):
    """Block-table paged path at the LOGITS level: chunked/bucketed
    prefill through the block pool, then paged decode steps, must match
    teacher forcing like the contiguous path does (granite = MQA,
    chatglm = GQA + partial rope + qkv bias; the MoE model with a leading
    dense layer, whose two layer stacks share the carried pool)."""
    import dataclasses
    from repro.models.param import is_def

    cfg = dataclasses.replace(get_smoke_config(arch),
                              first_dense_layers=dense)
    model = build_model(cfg)
    params = model.init(jax.random.key(7))
    B, T, extra = 1, 13, 3
    bs, chunk, num_blocks = 4, 8, 8
    full = _batch(model, T + extra, B, seed=7)
    ref_logits, _ = model.apply(params, full, mode="train")

    pages = jax.tree.map(lambda d: jnp.zeros(d.shape, d.dtype),
                         model.paged_cache_defs(num_blocks, bs),
                         is_leaf=is_def)
    # identity block table: position p lives in block p // bs
    bt = jnp.arange(num_blocks, dtype=jnp.int32)[None]          # (1, nb)

    # chunked prefill: [0, 8) full chunk, then [8, 13) padded to bucket 8
    logits = None
    pos = 0
    while pos < T:
        c = min(chunk, T - pos)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :c] = np.asarray(full["tokens"][0, pos: pos + c])
        pv = np.full((1, chunk), -1, np.int32)
        pv[0, :c] = np.arange(pos, pos + c)
        logits, pages = model.apply(
            params, {"tokens": jnp.asarray(toks),
                     "positions": jnp.asarray(pv),
                     "block_tables": bt,
                     "last_index": jnp.asarray([c - 1], jnp.int32)},
            mode="chunk_prefill", cache=pages)
        pos += c
    np.testing.assert_allclose(
        np.asarray(logits[:, 0], np.float32),
        np.asarray(ref_logits[:, T - 1], np.float32),
        rtol=2e-2, atol=2e-2)

    for i in range(extra):
        dec_in = {"tokens": full["tokens"][:, T + i: T + i + 1],
                  "positions": jnp.full((B, 1), T + i, jnp.int32),
                  "block_tables": bt}
        logits, pages = model.apply(params, dec_in, mode="decode",
                                    cache=pages)
        np.testing.assert_allclose(
            np.asarray(logits[:, 0], np.float32),
            np.asarray(ref_logits[:, T + i], np.float32),
            rtol=2e-2, atol=2e-2)


def test_multi_step_decode_consistent():
    """Three consecutive decode steps match teacher forcing (dense arch)."""
    cfg = get_smoke_config("granite-20b")
    model = build_model(cfg)
    params = model.init(jax.random.key(2))
    B, T, extra = 2, 12, 3
    full = _batch(model, T + extra, B)
    ref_logits, _ = model.apply(params, full, mode="train")

    pre = {"tokens": full["tokens"][:, :T]}
    _, cache = model.apply(params, pre, mode="prefill")
    for i in range(extra):
        dec_in = {"tokens": full["tokens"][:, T + i: T + i + 1],
                  "positions": jnp.full((B, 1), T + i, jnp.int32)}
        logits, cache = model.apply(params, dec_in, mode="decode",
                                    cache=cache)
        np.testing.assert_allclose(
            np.asarray(logits[:, 0], np.float32),
            np.asarray(ref_logits[:, T + i], np.float32),
            rtol=2e-2, atol=2e-2)
