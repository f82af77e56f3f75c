"""The latent-attention serve cell's harness pieces: its flop and byte
counts by hand, the configuration's parameter count, the roofline reader,
and a whole run of its driver at the program's smoke widths."""
import math

import pytest

from chipbench import common, latent_flops as lf
from chipbench.drivers import serve_closed_latent as drv
from conftest import ROOT, drive, small_serve_traffic

MOON = common.load_json(ROOT / "chipbench/configs/moonlight-16b-a3b.json")


@pytest.fixture
def moon_smoke():
    """moonlight-16b-a3b's file with the program's smoke widths."""
    c = dict(MOON)
    c.update(smoke=True, hidden_size=64, intermediate_size=128,
             moe_intermediate_size=32, num_hidden_layers=3,
             num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             n_routed_experts=4, n_routed_experts_published=8,
             num_experts_per_tok=3, vocab_size=503,
             serve_pool={"block_size": 16, "num_blocks": 48})
    return c


def test_parameter_count_of_the_share_and_the_whole():
    """8 of 64 experts held: 3.36 B parameters here; all 64: the published
    16 B."""
    count = lambda c: sum(math.prod(s)
                          for s in drv.expected_shapes(c).values())
    assert count(MOON) == 3_364_615_296
    assert count(dict(MOON, n_routed_experts=64)) == 15_960_110_208


def test_moonlight_flops_by_hand():
    # q 2048 x 16 x 192, latent + rope key 2048 x 576, out 16 x 128 x 2048
    proj = 2048 * 16 * 192 + 2048 * 576 + 16 * 128 * 2048
    assert lf.mla_projection_params(MOON) == proj == 11_665_408
    dense = 3 * 2048 * 11264
    # router 2048 x 64; 2 shared and 6 x 8 / 64 = 0.75 held experts a token
    moe = 2048 * 64 + 3 * 2048 * 1408 * 2.75
    assert lf.ffn_params_per_token(MOON, True) == dense
    assert lf.ffn_params_per_token(MOON, False) == moe
    per_pos = 27 * 16 * 2 * (2 * 512 + 64)
    assert lf.decode_attention_per_position(MOON) == per_pos == 940_032
    tok = 2 * 27 * proj + 2 * (dense + 26 * moe)
    head = 2 * 2048 * 163840
    absorb = 2 * 27 * 16 * 512 * (128 + 128)
    assert lf.decode(MOON, 1000) == tok + absorb + per_pos * 1000 + head
    T = 64
    expand = 2 * 27 * 512 * 16 * (128 + 128)
    pairs = 2 * 27 * 16 * (128 + 64 + 128) * T * (T + 1) / 2
    assert lf.prefill(MOON, T) == T * (tok + expand) + pairs + head
    # one block: 16 tokens x 576 bf16 values x 27 layers
    assert lf.latent_block_bytes(MOON, 16) == 16 * 576 * 2 * 27


def _reader():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "latent_roofline", ROOT / "chipbench/metrics/"
        "latent_attention_roofline.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_latent_roofline_reader():
    read = _reader()
    trace = {"device_kind": "TPU v5 lite",
             "ops": {"_decode_impl/paged_latent_attention": 0.25,
                     "_decode_impl/fusion": 3.0,
                     "_chunk_impl/paged_decode_attention": 9.0}}
    readings = {"trace": trace, "latent_block_bytes": 497_664,
                "counters": {"open": {"kv_blocks_read": 1000},
                             "close": {"kv_blocks_read": 101_000}}}
    want = 100.0 * 100_000 * 497_664 / 819e9 / 0.25
    assert read(readings) == pytest.approx(want)
    # nothing to read: no trace, no counters (a program before the
    # counter snapshot), no kernel in the trace
    assert read({**readings, "trace": None}) is None
    assert read({k: v for k, v in readings.items() if k != "counters"}) \
        is None
    no_kernel = {**trace, "ops": {"_decode_impl/fusion": 3.0}}
    assert read({**readings, "trace": no_kernel}) is None


def test_program_config_refuses_a_changed_width(moon_smoke):
    drv.program_config(moon_smoke)
    with pytest.raises(ValueError, match="kv_lora_rank"):
        drv.program_config(dict(moon_smoke, kv_lora_rank=16))


def test_sound_latent_serve_run_is_correct(moon_smoke):
    res = drive("serve_closed_latent", moon_smoke,
                small_serve_traffic("chat-closed64"), seconds=3.0)
    assert res["correct"] and res["failed"] == 0
    r = res["readings"]
    assert r["flops"] > 0 and r["counters"]["close"]["kv_blocks_read"] > \
        r["counters"]["open"]["kv_blocks_read"]
    assert res["e2e"]["tokens_per_s"] > 0


def test_limit_readings_of_the_latent_cell(moon_smoke):
    """The readings that set the cell's limit: the fp8 control reads a
    wider mean gap than the program, and every witness is reported."""
    t = small_serve_traffic("chat-closed64")
    r = drv.readings(moon_smoke, t, 2**31 + 23, 3.0)
    assert r["compared_tokens"] > 0 and r["wrong_lengths"] == 0
    assert r["correct"]
    assert r["control_fp8_logit_gap_mean"] > r["logit_gap_mean"]
    assert 0 <= r["widest_with_flip"] <= r["widest"] == min(
        drv.WIDEST, r["compared_tokens"])
    assert r["routing_flip_tokens"] <= r["compared_tokens"]
    for name in ("bf16_reference_", "bf16_stream_reference_"):
        assert r[f"{name}logit_gap_mean"] >= 0
