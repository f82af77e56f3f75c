"""The plain references against the program at sizes the CPU holds."""
import jax
import jax.numpy as jnp
import numpy as np

from chipbench import common, serving, weights
from chipbench.reference import qwen as ref
from conftest import drive, small_fl_traffic


def test_qwen_reference_matches_the_program_forward(qwen_smoke):
    from repro.models import build_model
    cfg = serving.program_config(qwen_smoke)
    model = build_model(cfg)
    w = weights.dense_decoder(qwen_smoke, common.seed_key(2**31 + 3),
                              jax.eval_shape(model.init, jax.random.key(0)))
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, 96)
    prog = model.apply(w, {"tokens": jnp.asarray(tokens[None])},
                       mode="train")[0][0].astype(jnp.float32)
    rows = np.arange(96)
    c = tuple(sorted((k, v) for k, v in qwen_smoke.items()
                     if isinstance(v, (int, float, bool, str))))
    got = ref.logits_at(w, jnp.asarray(tokens), jnp.asarray(rows), c=c)
    scale = float(jnp.abs(got).max())
    # bf16 program against the float32 reference: a few bf16 ulps
    assert float(jnp.abs(prog - got).max()) < 0.03 * scale
    agree = float((prog.argmax(1) == got.argmax(1)).mean())
    assert agree > 0.9
    # fp8 control departs further than the bf16 program
    f8 = ref.logits_at(w, jnp.asarray(tokens), jnp.asarray(rows), c=c,
                       precision="fp8")
    assert float(jnp.abs(f8 - got).max()) > float(jnp.abs(prog - got).max())


def test_fl_reference_follows_the_program_on_cpu():
    """On the CPU the program's float32 is float32 throughout, so the
    reference agrees to round-off."""
    cfg = common.load_json(common.ROOT / "chipbench/configs/"
                           "flight-cnn-cifar.json")
    res = drive("fl_sync", cfg, small_fl_traffic("cohort256"), seconds=1.0)
    assert res["correct"]
    for name, (value, _) in res["checks"].items():
        assert value < 1e-4, name
