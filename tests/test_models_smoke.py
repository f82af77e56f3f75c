"""Per-architecture smoke tests: reduced same-family config, one forward +
one real train step on CPU, asserting shapes and no NaNs (assignment SSf)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config, list_archs
from repro.launch.steps import make_train_step
from repro.models import build_model
from repro.models.config import SHAPES, ShapeConfig
from repro.optim import adamw

DEV = ShapeConfig("dev", "train", 32, 2)

ARCHS = list_archs()


def batch_for(model, shape, seed=0):
    rng = np.random.default_rng(seed)
    cfg = model.cfg
    b = {}
    for k, d in model.input_defs(shape).items():
        if d.dtype == jnp.int32:
            hi = cfg.vocab_size if k in ("tokens", "labels") else shape.seq_len
            b[k] = jnp.asarray(rng.integers(0, max(hi, 2), d.shape), jnp.int32)
        else:
            b[k] = jnp.asarray(rng.normal(size=d.shape), d.dtype)
    return b


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_shapes_no_nan(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = batch_for(model, DEV)
    logits, aux = model.apply(params, batch, mode="train")
    assert logits.shape == (DEV.global_batch, DEV.seq_len, cfg.vocab_size)
    assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step_updates_params(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    opt = adamw(1e-2)
    step = jax.jit(make_train_step(model, opt))
    params = model.init(jax.random.key(0))
    opt_state = opt.init(params)
    batch = batch_for(model, DEV)
    new_params, opt_state, metrics = step(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))
    # at least one leaf changed
    changed = any(
        not np.array_equal(np.asarray(a, np.float32),
                           np.asarray(b, np.float32))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(new_params)))
    assert changed


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_assignment(arch):
    """The FULL configs carry the exact assigned hyperparameters."""
    cfg = get_config(arch)
    expected = {
        "mixtral-8x22b": (56, 6144, 48, 8, 32768),
        "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 151936),
        "qwen1.5-4b": (40, 2560, 20, 20, 151936),
        "chatglm3-6b": (28, 4096, 32, 2, 65024),
        "granite-20b": (52, 6144, 48, 1, 49152),
        "minitron-8b": (32, 4096, 32, 8, 256000),
        "phi-3-vision-4.2b": (32, 3072, 32, 32, 32064),
        "recurrentgemma-9b": (38, 4096, 16, 1, 256000),
        "falcon-mamba-7b": (64, 4096, 0, 0, 65024),
        "seamless-m4t-large-v2": (24, 1024, 16, 16, 256206),
        "moonlight-16b-a3b": (27, 2048, 16, 16, 163840),
    }[arch]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.vocab_size)
    assert got == expected, (arch, got, expected)


def test_moe_active_params_less_than_total():
    m = build_model(get_config("mixtral-8x22b"))
    assert m.n_active_params < m.n_params
    # 8 experts top-2: expert params scale ~2/8
    q = build_model(get_config("qwen3-moe-235b-a22b"))
    assert q.n_active_params < 0.2 * q.n_params


def test_param_counts_in_expected_range():
    """Sanity: derived parameter counts match the models' nominal sizes."""
    expect = {
        "mixtral-8x22b": (130e9, 150e9),
        "qwen3-moe-235b-a22b": (200e9, 260e9),
        "qwen1.5-4b": (3e9, 5e9),
        "chatglm3-6b": (5.5e9, 7.5e9),
        "granite-20b": (18e9, 23e9),
        "minitron-8b": (7e9, 10.5e9),
        "phi-3-vision-4.2b": (3.3e9, 4.6e9),
        "recurrentgemma-9b": (7.5e9, 11e9),
        "falcon-mamba-7b": (6e9, 8.5e9),
        "seamless-m4t-large-v2": (0.8e9, 1.8e9),
        "moonlight-16b-a3b": (15.5e9, 16.5e9),
    }
    for arch, (lo, hi) in expect.items():
        n = build_model(get_config(arch)).n_params
        assert lo <= n <= hi, (arch, n)
