"""Moonlight-16B-A3B [moe, deepseek_v3 blocks] -- 27L d_model=2048 16H,
latent attention (MLA: kv_lora_rank 512, qk 128 + 64 rope, v 128, plain q
projection), one leading dense layer (d_ff 11264), then 26 MoE layers of
64 routed experts (width 1408, 6 a token) and 2 shared experts, routed by
a bias-corrected sigmoid (noaux_tc, scaling 2.446); vocab 163840, untied.
[hf:moonshotai/Moonlight-16B-A3B config.json; arXiv:2412.19437, 2405.04434]

CONFIG is the whole model; CHIP_SHARE is what one chip of an 8-chip
expert-parallel deployment holds (experts 0-7 of every layer, attention,
shared experts, dense layer and vocabulary whole)."""
import dataclasses

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=11264, vocab_size=163840,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    num_experts=64, experts_per_token=6, moe_d_ff=1408,
    n_shared_experts=2, first_dense_layers=1,
    router="sigmoid", routed_scaling=2.446, capacity_factor=0.0,
    rope_theta=50_000.0, norm="rmsnorm", norm_eps=1e-5, act="silu",
)

#: one chip's share of 8 chips that divide each layer's 64 experts
CHIP_SHARE = dataclasses.replace(CONFIG, held_experts=(0, 8))

SMOKE = ModelConfig(
    name="moonlight-16b-a3b-smoke", family="moe",
    num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
    d_ff=128, vocab_size=503,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16,
    num_experts=8, experts_per_token=3, moe_d_ff=32,
    n_shared_experts=2, first_dense_layers=1,
    router="sigmoid", routed_scaling=2.446, capacity_factor=0.0,
    held_experts=(0, 4),
    rope_theta=50_000.0, norm="rmsnorm", norm_eps=1e-5, act="silu",
    remat=False,
)
