"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed without a chip: a described `v5e:2x2`
topology lets XLA and Mosaic compile (not run) for it, and refuse what
the chip's compiler would refuse -- a kernel that needs more VMEM than it
may use, or one GSPMD would have to partition.  Interpret mode is forced
off inside each test: the ops layer picks it from the CPU backend.

The topology is described inside a module fixture, never at import, so
every test worker collects the same tests and only the worker given this
file loads the TPU library.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a TPU compile written to the persistent cache cannot be read
        # back without a chip; keep these compiles out of it
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        try:
            yield t
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("shape", [
    (256, 2560),      # qwen1.5-4b d_model rows
    (256, 6912),      # qwen1.5-4b d_ff rows
    (256, 151936),    # qwen1.5-4b vocabulary rows (untied unembed)
    (300, 5000),      # partial row tile x column tiles with a masked tail
    (4 * 20, 128),    # int8 KV-cache decode write: B * Hkv rows of head_dim
])
def test_quant8_compiles_for_v5e(one_chip, shape):
    from repro.kernels.quant8.kernel import dequantize_blocked, quantize_blocked
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    q = jax.ShapeDtypeStruct(shape, jnp.int8, sharding=one_chip)
    s = jax.ShapeDtypeStruct((shape[0], 1), jnp.float32, sharding=one_chip)
    quant = jax.jit(functools.partial(quantize_blocked, interpret=False))
    dequant = jax.jit(functools.partial(dequantize_blocked, interpret=False,
                                        out_dtype=jnp.bfloat16))
    assert "tpu_custom_call" in quant.lower(x).compile().as_text()
    assert "tpu_custom_call" in dequant.lower(q, s).compile().as_text()


def test_q8_exchange_compiles_with_islands_over_pod(topo, monkeypatch):
    """The compressed island exchange with the island axis sharded over a
    4-device `pod` axis: the Pallas quantise must sit inside a shard_map
    (Mosaic kernels cannot be partitioned) and reach the program."""
    from repro.core.federated import fl_aggregate_compressed
    from repro.kernels.quant8 import ops as q8ops
    from repro.launch.mesh import make_mesh
    monkeypatch.setattr(q8ops, "_use_interpret", lambda: False)
    mesh = make_mesh((4,), ("pod",), devices=topo.devices)
    leaf = jax.ShapeDtypeStruct((4, 2048, 3072), jnp.bfloat16,
                                sharding=NamedSharding(mesh,
                                                       PartitionSpec("pod")))
    mixing = jax.ShapeDtypeStruct((4, 4), jnp.float32,
                                  sharding=NamedSharding(mesh,
                                                         PartitionSpec()))
    step = jax.jit(functools.partial(fl_aggregate_compressed, mode="q8",
                                     impl="pallas"))
    with jax.set_mesh(mesh):
        text = step.lower(leaf, leaf, mixing).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape", [(256, 1024), (256, 4096)])
def test_quant8_kernels_carry_their_names(one_chip, shape):
    """The compiled custom call is named after the kernel (one column
    tile, then column tiles), so a profiler trace finds the kernel's
    operation by that name in whatever program runs it."""
    from repro.kernels.quant8.kernel import (DEQUANT_NAME, QUANT_NAME,
                                             dequantize_blocked,
                                             quantize_blocked)
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    q = jax.ShapeDtypeStruct(shape, jnp.int8, sharding=one_chip)
    s = jax.ShapeDtypeStruct((shape[0], 1), jnp.float32, sharding=one_chip)
    calls = lambda text: re.findall(r"%(\w+?)(?:\.\d+)? = .*custom-call\(",
                                    text)
    quant = jax.jit(functools.partial(quantize_blocked, interpret=False))
    dequant = jax.jit(functools.partial(dequantize_blocked, interpret=False))
    assert calls(quant.lower(x).compile().as_text()) == [QUANT_NAME]
    assert calls(dequant.lower(q, s).compile().as_text()) == [DEQUANT_NAME]


@pytest.mark.parametrize("B,H,Hkv,BS", [
    (8, 20, 20, 16),      # qwen1.5-4b: 20 KV heads
    (8, 32, 2, 16),       # chatglm3-6b: 16 query heads per KV head
    (4, 16, 4, 32),
])
def test_paged_attention_kernel_compiles_for_v5e(one_chip, B, H, Hkv, BS):
    from repro.kernels.paged_attention.kernel import (NAME,
                                                      paged_decode_attention)
    S = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                  sharding=one_chip)
    pool = S((40, 512, Hkv, BS, 128), jnp.bfloat16)
    step = jax.jit(functools.partial(paged_decode_attention,
                                     interpret=False))
    text = step.lower(S((B, 1, H, 128), jnp.bfloat16), pool, pool,
                      S((), jnp.int32), S((B, 512), jnp.int32),
                      S((B,), jnp.int32)).compile().as_text()
    assert NAME in text and "tpu_custom_call" in text


def test_paged_decode_step_reads_the_pool_through_the_kernel(one_chip,
                                                            monkeypatch):
    """PagedServeLoop's decode step at qwen1.5-4b's widths (40 layers, a
    512 x 16 pool, batch 8), compiled for a v5e as a TPU backend would
    run it: the paged attention is the named kernel, and the gathered
    (B * nbmax * BS, Hkv, D) f32 copy of K is gone from the program."""
    from repro.configs import get_config
    from repro.kernels.paged_attention import ops
    from repro.launch.serve_loop import PagedServeLoop
    from repro.models import build_model
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = get_config("qwen1.5-4b")
    model = build_model(cfg)
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                             sharding=one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(model.init,
                                                  jax.random.key(0)))
    B, NB, BS = 8, 512, 16
    pool = jax.ShapeDtypeStruct(
        (cfg.num_layers, NB, cfg.num_kv_heads, BS, cfg.head_dim),
        jnp.bfloat16, sharding=one_chip)
    i32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                             sharding=one_chip)
    # the step needs only the model; no pool is allocated here
    loop = PagedServeLoop.__new__(PagedServeLoop)
    loop.model, loop.rules = model, None
    text = jax.jit(loop._decode_impl, donate_argnums=(1,)).lower(
        params, {"kp": pool, "vp": pool}, i32((B, NB)), i32((B, 1)),
        i32((B, 1))).compile().as_text()
    assert re.search(r"%paged_decode_attention(\.\d+)? = .*custom-call\(",
                     text)
    gathered = f"f32[{B * NB * BS},{cfg.num_kv_heads},{cfg.head_dim}]"
    assert gathered not in text


@pytest.mark.parametrize("step,temp_limit", [("_decode_impl", 64 << 20),
                                             ("_chunk_impl", 128 << 20)])
def test_paged_steps_carry_the_pool_in_place(one_chip, monkeypatch, step,
                                             temp_limit):
    """PagedServeLoop's decode and chunk-prefill steps at qwen1.5-4b's
    widths (40 layers, a 512 x 16 pool, batch 8, chunks of 64), compiled
    for a v5e: the layer scan carries the stacked head-major pool and
    writes whole blocks into it, so the pool is never copied, no layer's
    pool is sliced out, and the temporaries hold no second pool."""
    from repro.configs import get_config
    from repro.kernels.paged_attention import ops
    from repro.launch.serve_loop import PagedServeLoop
    from repro.models import build_model
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = get_config("qwen1.5-4b")
    model = build_model(cfg)
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                             sharding=one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(model.init,
                                                  jax.random.key(0)))
    B, NB, BS, C = 8, 512, 16, 64
    layer_pool = (NB, cfg.num_kv_heads, BS, cfg.head_dim)
    shape = (cfg.num_layers,) + layer_pool
    pool = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    i32 = lambda s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    loop = PagedServeLoop.__new__(PagedServeLoop)
    loop.model, loop.rules = model, None
    pages = {"kp": pool, "vp": pool}
    if step == "_decode_impl":
        args = (params, pages, i32((B, NB)), i32((B, 1)), i32((B, 1)))
    else:
        args = (params, pages, i32((1, C)), i32((1, C)), i32((1, NB)),
                i32((1,)))
    compiled = jax.jit(getattr(loop, step), donate_argnums=(1,)).lower(
        *args).compile()
    text = compiled.as_text()
    typed = lambda s: re.escape("bf16[" + ",".join(map(str, s)) + "]")
    assert not re.search(typed(shape) + r"\{[^}]*\} copy\(", text)
    assert not re.search(typed(layer_pool) + r"\{[^}]*\} dynamic-slice\(",
                         text)
    if step == "_decode_impl":
        assert re.search(
            r"%paged_decode_attention(\.\d+)? = .*custom-call\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < temp_limit


@pytest.mark.parametrize("B,H,W,BS", [
    (64, 16, 640, 16),    # moonlight-16b-a3b: 576-value rows in 640 lanes
    (8, 8, 256, 32),
])
def test_paged_latent_attention_kernel_compiles_for_v5e(one_chip, B, H, W,
                                                       BS):
    from repro.kernels.paged_attention.latent import (NAME,
                                                      paged_latent_attention)
    S = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                  sharding=one_chip)
    step = jax.jit(functools.partial(paged_latent_attention, scale=0.07,
                                     dv=W - 128, interpret=False))
    text = step.lower(S((B, H, W), jnp.bfloat16),
                      S((27, 512, BS, W), jnp.bfloat16), S((), jnp.int32),
                      S((B, 160), jnp.int32), S((B,), jnp.int32)
                      ).compile().as_text()
    assert NAME in text and "tpu_custom_call" in text


def test_latent_decode_step_carries_the_pool_in_place(one_chip, monkeypatch):
    """PagedServeLoop's decode step for moonlight-16b-a3b's chip share (27
    layers, 8 held experts, a 1024 x 16 latent pool, batch 64), compiled
    for a v5e: attention is the named latent kernel, and the stacked pool
    is never copied (the layer scans carry it and write it in place)."""
    from repro.configs.moonlight_16b_a3b import CHIP_SHARE as cfg
    from repro.kernels.paged_attention import ops
    from repro.launch.serve_loop import PagedServeLoop
    from repro.models import build_model
    from repro.models.cache import latent_lanes
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    model = build_model(cfg)
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                             sharding=one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(model.init,
                                                  jax.random.key(0)))
    B, NB, BS = 64, 1024, 16
    shape = (cfg.num_layers, NB, BS, latent_lanes(cfg))
    pool = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    i32 = lambda s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    loop = PagedServeLoop.__new__(PagedServeLoop)
    loop.model, loop.rules = model, None
    compiled = jax.jit(loop._decode_impl, donate_argnums=(1,)).lower(
        params, {"latent": pool}, i32((B, 160)), i32((B, 1)),
        i32((B, 1))).compile()
    text = compiled.as_text()
    assert re.search(r"%paged_latent_attention(\.\d+)? = .*custom-call\(",
                     text)
    pool_type = "bf16[" + ",".join(map(str, shape)) + "]"
    assert not re.search(re.escape(pool_type) + r"\{[^}]*\} copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
