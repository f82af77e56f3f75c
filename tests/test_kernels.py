"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(interpret=True on CPU, per the assignment)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

rng = np.random.default_rng(42)


# ---------------- fed_agg ----------------

@pytest.mark.parametrize("K", [1, 2, 5, 8])
@pytest.mark.parametrize("n", [128, 2048, 5000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fed_agg_sweep(K, n, dtype):
    from repro.kernels.fed_agg.ops import fed_agg
    from repro.kernels.fed_agg.ref import fed_agg_2d_ref
    x = jnp.asarray(rng.normal(size=(K, n)), dtype)
    w = jnp.asarray(rng.dirichlet([1.0] * K), jnp.float32)
    got = fed_agg(x, w)
    want = fed_agg_2d_ref(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_fed_agg_tree_matches_weighted_average():
    from repro.core.aggregation import weighted_average
    from repro.kernels.fed_agg.ops import fed_agg_tree
    trees = [{"a": jnp.asarray(rng.normal(size=(33, 7)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(130,)), jnp.bfloat16)}
             for _ in range(3)]
    w = [0.2, 0.5, 0.3]
    got = fed_agg_tree(trees, w)
    want = weighted_average(trees, w)
    for g, x in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(x, np.float32),
                                   rtol=2e-2, atol=2e-2)


# ---------------- quant8 ----------------

@pytest.mark.parametrize("n", [64, 256, 1000, 4096])
@pytest.mark.parametrize("scale", [0.01, 1.0, 100.0])
def test_quant8_sweep(n, scale):
    from repro.core.compression import dequantize_blockwise, quantize_blockwise
    from repro.kernels.quant8.ops import dequantize, quantize
    x = jnp.asarray(rng.normal(size=(n,)) * scale, jnp.float32)
    q, s = quantize(x)
    qr, sr = quantize_blockwise(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    got = dequantize(q, s, (n,))
    want = dequantize_blockwise(qr, sr, (n,))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("impl", ["auto", "ref"])   # auto = interpret Pallas
@pytest.mark.parametrize("n", [100, 257, 1000])     # non-multiples of BLOCK
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant8_nonmultiple_and_bf16(impl, n, dtype):
    """Kernel<->reference parity on sizes that force the pad path and on
    bf16 inputs, in both interpret and ref modes."""
    from repro.core.compression import dequantize_blockwise, quantize_blockwise
    from repro.kernels.quant8.ops import dequantize, quantize
    x = jnp.asarray(rng.normal(size=(n,)) * 3.0, dtype)
    q, s = quantize(x, impl=impl)
    qr, sr = quantize_blockwise(x)
    # bf16 values sitting exactly on a rounding boundary may round one ulp
    # apart between the kernel and the reference; never more than that
    dq = np.abs(np.asarray(q, np.int32) - np.asarray(qr, np.int32))
    assert dq.max() <= (0 if dtype == jnp.float32 else 1)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    got = dequantize(q, s, (n,), impl=impl)
    want = dequantize_blockwise(qr, sr, (n,))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=float(np.max(np.asarray(sr))))


@pytest.mark.parametrize("impl", ["auto", "ref"])
def test_quant8_zero_delta_scale_clamp(impl):
    """All-zero input exercises the scale clamp: q == 0, scale == 0, and
    the roundtrip returns exact zeros (no NaN from the 0/0 guard)."""
    from repro.kernels.quant8.ops import dequantize, quantize
    x = jnp.zeros((777,), jnp.float32)
    q, s = quantize(x, impl=impl)
    assert not np.asarray(q).any() and not np.asarray(s).any()
    np.testing.assert_array_equal(np.asarray(dequantize(q, s, (777,),
                                                        impl=impl)), 0.0)


@pytest.mark.parametrize("impl", ["auto", "ref"])
@pytest.mark.parametrize("shape", [
    (5, 7), (3, 300), (1000,), (2, 3, 130),
    (600, 1000),      # several row tiles, the last one partial
    (3, 5000),        # rows wider than one column tile: running absmax
    (300, 2100),      # column tiles x partial row tiles, masked tail
    (2, 151936),      # qwen1.5-4b vocabulary width, 75 column tiles
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant8_rowwise_matches_reference(impl, shape, dtype):
    """The sharding-preserving rowwise layout (per last-dim channel):
    same shape out, exact parity with core.compression's reference, for
    channel counts that are not lane multiples, rows that span several
    column tiles (the two-phase running-absmax grid) and bf16 inputs.
    Both sides are compiled, as inside the exchange: run op by op, the
    reference's `amax / 127` is a true division that may land one ulp off
    the compiled reciprocal multiply."""
    from repro.core import compression as comp
    from repro.kernels.quant8.ops import dequantize_rowwise, quantize_rowwise
    x = jnp.asarray(rng.normal(size=shape) * 2.0, dtype)
    q, s = jax.jit(lambda v: quantize_rowwise(v, impl=impl))(x)
    qr, sr = jax.jit(comp.quantize_rowwise)(x)
    assert q.shape == x.shape and s.shape == x.shape[:-1] + (1,)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))
    got = dequantize_rowwise(q, s, impl=impl)
    want = comp.dequantize_rowwise(qr, sr)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------- flash attention ----------------

@pytest.mark.parametrize("T,H,Hkv,D,window,bq,bk", [
    (256, 4, 4, 64, 0, 128, 128),     # MHA causal
    (256, 4, 2, 64, 0, 128, 64),      # GQA, uneven blocks
    (512, 8, 1, 128, 0, 256, 256),    # MQA, D=128
    (512, 4, 2, 64, 128, 128, 128),   # sliding window
    (1024, 2, 2, 64, 300, 256, 256),  # window not block-aligned
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(T, H, Hkv, D, window, bq, bk, dtype):
    from repro.kernels.flash_attention.ops import flash_attention
    B = 2
    q = jnp.asarray(rng.normal(size=(B, T, H, D)) * 0.3, dtype)
    k = jnp.asarray(rng.normal(size=(B, T, Hkv, D)) * 0.3, dtype)
    v = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), dtype)
    got = flash_attention(q, k, v, causal=True, window=window, bq=bq, bk=bk)
    want = flash_attention(q, k, v, causal=True, window=window, impl="ref")
    tol = 3e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_matches_model_xla_path():
    """Kernel vs the model's pure-XLA blockwise attention."""
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.models.layers import flash_attention_xla
    B, T, H, D = 2, 512, 4, 64
    q = jnp.asarray(rng.normal(size=(B, T, H, D)) * 0.3, jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, H, D)) * 0.3, jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    a = flash_attention(q, k, v, causal=True, bq=128, bk=128)
    b = flash_attention_xla(q, k, v, causal=True, q_block=128, kv_block=128)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-4,
                               atol=3e-4)


# ---------------- linrec ----------------

@pytest.mark.parametrize("B,T,D,bt,bd", [
    (1, 128, 128, 64, 128),
    (2, 512, 640, 256, 128),
    (3, 256, 512, 64, 512),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_linrec_sweep(B, T, D, bt, bd, dtype):
    from repro.kernels.linrec.ops import linrec
    a = jnp.asarray(rng.uniform(0.7, 0.999, size=(B, T, D)), dtype)
    b = jnp.asarray(rng.normal(size=(B, T, D)) * 0.1, dtype)
    got = linrec(a, b, bt=bt, bd=bd)
    want = linrec(a, b, impl="ref")
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_linrec_matches_model_chunked_scan():
    from repro.kernels.linrec.ops import linrec
    from repro.models.ssm import _chunked_linear_scan
    B, T, D = 2, 256, 128
    a = jnp.asarray(rng.uniform(0.8, 0.999, size=(B, T, D)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(B, T, D)), jnp.float32)
    got = linrec(a, b, bt=64, bd=128)
    want, _ = _chunked_linear_scan(a, b, jnp.zeros((B, D)), chunk=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
