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
