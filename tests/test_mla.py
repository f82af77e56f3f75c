"""Moonlight-16B-A3B's blocks (deepseek_v3: latent attention, a leading
dense layer, a shared-expert MoE with a bias-corrected sigmoid router and
held experts) against the plain float32 reference the benchmark uses,
chipbench/reference/moonlight.py, loaded here from that file.

Weights are the SMOKE config's, drawn from a seed, with a non-zero
e_score_correction_bias.  Where the test casts them to float32, the
program and the reference compute alike up to summation order, except
where the program stores latent rows: the paged pool is bf16."""
import functools
import importlib.util
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels.paged_attention import ops
from repro.launch.serve_loop import PagedServeLoop, Request
from repro.models import build_model
from repro.models import layers as L

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location(
    "moonlight_reference", ROOT / "chipbench/reference/moonlight.py")
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

CFG = get_smoke_config("moonlight-16b-a3b")

#: float32 program against the float32 reference: they differ only in
#: summation order and the MoE's gather dispatch (measured 2.4e-6 on
#: logits of magnitude ~4); 1e-4 leaves that 40x room.
F32_TOL = 1e-4
#: paged paths, float32 weights: the latent rows are stored in the bf16
#: pool (a relative rounding of 2^-9 on c_kv and k_pe), and the jnp
#: reference path rounds the softmax weights to the query's dtype;
#: measured 6.2e-3 on these logits, on either decode path; 2e-2 is three
#: times that.
PAGED_TOL = 2e-2


def ref_config(cfg) -> tuple:
    return tuple(sorted({
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "num_experts_per_tok": cfg.experts_per_token,
        "routed_scaling_factor": cfg.routed_scaling}.items()))


def make_params(cfg, seed=0, dtype=jnp.float32):
    model = build_model(cfg)
    p = model.init(jax.random.key(seed))
    bias = p["layers"]["moe"]["e_bias"]
    p["layers"]["moe"]["e_bias"] = 0.1 * jax.random.normal(
        jax.random.key(seed + 100), bias.shape, jnp.float32)
    return model, jax.tree.map(
        lambda a: a if a.dtype == jnp.float32 else a.astype(dtype), p)


def ref_logits(params, cfg, tokens, rows, **kw):
    return ref.logits_at(params, jnp.asarray(tokens, jnp.int32),
                         jnp.asarray(rows, jnp.int32), c=ref_config(cfg),
                         held_first=cfg.experts_here[0], **kw)


@pytest.fixture(scope="module")
def smoke():
    model, params = make_params(CFG)
    toks = np.random.default_rng(0).integers(0, CFG.vocab_size, 40)
    return model, params, toks.astype(np.int32)


@pytest.mark.parametrize("mode", ["train", "prefill"])
def test_expanded_forward_matches_reference(smoke, mode):
    """Train (every position) and prefill (the last) run expanded MLA."""
    model, params, toks = smoke
    logits, _ = model.apply(params, {"tokens": jnp.asarray(toks)[None]},
                            mode=mode)
    rows = np.arange(len(toks)) if mode == "train" else [len(toks) - 1]
    want = ref_logits(params, CFG, toks, rows)
    assert logits.shape[1] == len(rows)
    err = float(jnp.abs(logits[0] - want).max())
    assert err <= F32_TOL, err


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_paged_paths_match_reference(smoke, impl, monkeypatch):
    """Chunked prefill (absorbed, over the slot's gathered latent rows, a
    bucketed tail included) then teacher-forced decode steps (absorbed,
    through the kernel in interpret mode or its jnp reference) against
    the reference's logits at the same positions."""
    monkeypatch.setattr(ops, "paged_latent_attention", functools.partial(
        ops.paged_latent_attention, impl=impl))
    model, params, toks = smoke
    chunk_step, decode_step = (jax.jit(functools.partial(
        lambda p, b, c, mode: model.apply(p, b, mode=mode, cache=c),
        mode=m)) for m in ("chunk_prefill", "decode"))
    bs, nb, nbmax = 8, 16, 6
    pool = {"latent": jnp.zeros(model.paged_cache_defs(
        1, nb, bs, nbmax)["latent"].shape, jnp.bfloat16)}
    bt = jnp.asarray([[5, 2, 9, 12, 7, 3]], jnp.int32)
    T, chunk = 27, 16
    out = {}
    for start in range(0, T, chunk):
        c = min(chunk, T - start)
        pos = np.full((1, chunk), -1, np.int32)
        pos[0, :c] = np.arange(start, start + c)
        tk = np.zeros((1, chunk), np.int32)
        tk[0, :c] = toks[start:start + c]
        lg, pool = chunk_step(params, {
            "tokens": jnp.asarray(tk), "positions": jnp.asarray(pos),
            "block_tables": bt, "last_index": jnp.asarray([c - 1])}, pool)
        out[start + c - 1] = lg[0, 0]
    Lyr = CFG.num_layers
    for t in range(T, len(toks) - 1):
        cache = {"latent": pool["latent"],
                 "bt": jnp.broadcast_to(bt[None], (Lyr,) + bt.shape),
                 "len": jnp.full((Lyr, 1), t, jnp.int32)}
        lg, cache = decode_step(params, {
            "tokens": jnp.asarray([[toks[t]]]),
            "positions": jnp.asarray([[t]])}, cache)
        pool = {"latent": cache["latent"]}
        out[t] = lg[0, 0]
    rows = sorted(out)
    want = ref_logits(params, CFG, toks, rows)
    got = jnp.stack([out[r] for r in rows])
    err = float(jnp.abs(got - want).max())
    assert err <= PAGED_TOL, err


def test_latent_kernel_matches_reference():
    """The kernel (interpret mode) against its gather reference: ragged
    lengths over two layers of the stacked pool, a shared block, and
    zeros for a free slot."""
    rng = np.random.default_rng(3)
    Lyr, NB, BS, W, H, r = 3, 24, 16, 256, 4, 160
    lens = np.asarray([0, 1, 15, 16, 17, 150], np.int32)
    bt = np.zeros((len(lens), 10), np.int32)
    free = list(rng.permutation(NB))
    for b, n in enumerate(lens):
        bt[b, :-(-n // BS)] = [free.pop() for _ in range(-(-n // BS))]
    bt[2, 0] = bt[3, 0]                                  # a shared block
    q = jnp.asarray(rng.normal(size=(len(lens), H, W)), jnp.bfloat16)
    pool = jnp.asarray(rng.normal(size=(Lyr, NB, BS, W)), jnp.bfloat16)
    live = lens > 0
    for layer in (0, 2):
        args = (q, pool, jnp.int32(layer), jnp.asarray(bt),
                jnp.asarray(lens))
        got = np.asarray(ops.paged_latent_attention(
            *args, scale=0.1, dv=r, impl="pallas"), np.float32)
        want = np.asarray(ops.paged_latent_attention(
            *args, scale=0.1, dv=r, impl="ref"), np.float32)
        assert got.shape == (len(lens), H, r)
        # the reference rounds the softmax weights to bf16, the kernel
        # keeps them f32: ~2^-9 of values of size ~1
        assert np.abs(got[live] - want[live]).max() <= 2e-2
        assert not got[~live].any()


def test_latent_auto_keeps_the_reference_off_the_tpu(monkeypatch):
    calls = []
    monkeypatch.setattr(ops.latent, "paged_latent_attention",
                        lambda *a, **k: calls.append(k) or a[0])
    q = jnp.zeros((2, 4, 128), jnp.bfloat16)
    pool = jnp.zeros((2, 8, 16, 128), jnp.bfloat16)
    args = (jnp.int32(1), jnp.zeros((2, 3), jnp.int32),
            jnp.ones((2,), jnp.int32))
    ops.paged_latent_attention(q, pool, *args, scale=1.0, dv=64)
    assert calls == []
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    ops.paged_latent_attention(q, pool, *args, scale=1.0, dv=64)
    assert calls == [{"scale": 1.0, "dv": 64, "interpret": False}]
    for q_, p_ in ((q[..., :96], pool[..., :96]),        # W % 128
                   (q, pool[:, :, :8]),                  # BS % 16
                   (q, pool.astype(jnp.float32))):       # pool dtype
        ops.paged_latent_attention(q_, p_, *args, scale=1.0, dv=64)
    assert len(calls) == 1


def moe_params(cfg, seed=1):
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    k = iter(jax.random.split(jax.random.key(seed), 8))
    n = lambda *s: jax.random.normal(next(k), s, jnp.float32) / np.sqrt(s[-2])
    fs = cfg.n_shared_experts * f
    return {"w_router": n(d, E),
            "e_bias": 0.1 * jax.random.normal(next(k), (E,), jnp.float32),
            "w_gate": n(E, d, f), "w_up": n(E, d, f), "w_down": n(E, f, d),
            "shared": {"w_gate": n(d, fs), "w_up": n(d, fs),
                       "w_down": n(fs, d)}}


@pytest.mark.parametrize("shares", [2, 4])
def test_expert_shares_sum_to_the_uncut_layer(shares):
    """Each chip's share computes its held experts plus the shared ones;
    summed over the shares, the shared experts counted once, they give
    the uncut layer, which is the reference's dense weighted sum."""
    import dataclasses
    whole = dataclasses.replace(CFG, held_experts=())
    p = moe_params(whole)
    x = jax.random.normal(jax.random.key(7), (2, 9, CFG.d_model), jnp.float32)
    uncut, _ = L.moe_apply(p, whole, x)
    E, n = whole.num_experts, whole.num_experts // shares
    total = -(shares - 1) * L.mlp_apply(p["shared"], whole, x)
    for s in range(shares):
        cfg = dataclasses.replace(whole, held_experts=(s * n, n))
        part = {**p, **{w: p[w][s * n:(s + 1) * n]
                        for w in ("w_gate", "w_up", "w_down")}}
        y, _ = L.moe_apply(part, cfg, x)
        total = total + y
    assert float(jnp.abs(total - uncut).max()) <= 1e-5
    with jax.default_matmul_precision("highest"):
        want, _ = ref._moe(x.reshape(-1, CFG.d_model), p,
                           dict(ref_config(whole)), lambda a: a, 0)
    assert float(jnp.abs(uncut.reshape(want.shape) - want).max()) <= 1e-5


def test_router_selection_with_bias_matches_reference():
    """The sigmoid router picks the top k of sigmoid + bias and weights the
    choices by the unbiased sigmoid, normalised and scaled, as the
    reference; the bias changes which experts are picked."""
    p = moe_params(CFG)
    x = jax.random.normal(jax.random.key(8), (1, 64, CFG.d_model),
                          jnp.float32)
    gval, gidx, _ = L.moe_route(p, CFG, x)
    wt, idx = ref.route(x[0], p, dict(ref_config(CFG)), lambda a: a)
    np.testing.assert_array_equal(np.sort(gidx[0], -1), idx)
    got = jnp.zeros_like(wt).at[jnp.arange(64)[:, None], gidx[0]].set(gval[0])
    assert float(jnp.abs(got - wt).max()) <= 1e-6
    np.testing.assert_allclose(got.sum(-1), CFG.routed_scaling, rtol=1e-5)
    _, unbiased, _ = L.moe_route({**p, "e_bias": 0 * p["e_bias"]}, CFG, x)
    assert (np.sort(unbiased[0], -1) != idx).any()


def served_gaps(params, cfg, done):
    gaps = []
    for r in done:
        seq = np.concatenate([r.prompt, r.out[:-1]]).astype(np.int32)
        rows = np.arange(len(r.prompt) - 1, len(seq))
        lg = ref_logits(params, cfg, seq, rows)
        chosen = jnp.take_along_axis(lg, jnp.asarray(r.out)[:, None], 1)[:, 0]
        gaps.append(np.asarray(lg.max(1) - chosen))
    return np.concatenate(gaps)


def test_paged_loop_serves_within_tolerance_of_reference():
    """A PagedServeLoop drain of the SMOKE model in bf16, as served, with
    mid-flight joins and preemption: teacher-forced through the float32
    reference, every served token's logit lies within GAP of the best.
    GAP: bf16 weights and activations round the logits (measured widest
    gap 0.132); serving with the router's bias left out reads 0.367, and
    with the shared experts left out 2.78."""
    GAP = 0.25
    model, params = make_params(CFG, seed=2, dtype=jnp.bfloat16)
    rng = np.random.default_rng(2)
    loop = PagedServeLoop(model, params, max_batch=3, num_blocks=9,
                          block_size=8, chunk=16, max_seq_len=64)
    for i, n in enumerate((21, 23, 22, 9)):
        loop.submit(Request(rid=i, prompt=rng.integers(
            0, CFG.vocab_size, n).astype(np.int32), max_new=16))
    done = loop.run_until_drained()
    assert loop.counters["preemptions"] >= 1 and len(done) == 4
    assert all(len(r.out) == 16 for r in done)
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    widest = served_gaps(f32, CFG, done).max()
    assert widest <= GAP, widest


def test_max_seq_len_bounds_the_tables():
    """The loop's block tables are max_seq_len wide, not the pool's; a
    request longer than that is refused."""
    model, params = make_params(CFG, dtype=jnp.bfloat16)
    loop = PagedServeLoop(model, params, max_batch=2, num_blocks=32,
                          block_size=8, chunk=16, max_seq_len=40)
    assert loop.nbmax == 5 and loop.bt.shape == (2, 5)
    assert PagedServeLoop(model, params, max_batch=2, num_blocks=32,
                          block_size=8, chunk=16).nbmax == 32
    loop.submit(Request(rid=0, prompt=np.zeros(30, np.int32), max_new=11))
    with pytest.raises(ValueError, match="max_seq_len"):
        loop.tick()


def test_paged_support_is_derived_from_the_cache():
    """A windowed config is refused with a reason before a loop exists;
    MLA's latent pool pages, a stateful family does not."""
    mixtral = build_model(get_smoke_config("mixtral-8x22b"))
    assert not mixtral.supports_paged_cache
    with pytest.raises(ValueError, match="window"):
        PagedServeLoop(mixtral, None)
    assert build_model(CFG).supports_paged_cache
    assert not build_model(get_smoke_config("falcon-mamba-7b")
                           ).supports_paged_cache


def test_latent_decode_step_named_scopes():
    """The MLA decode step's ops carry latent_write and mla_absorb inside
    attention, and router / routed_experts / shared_experts inside moe."""
    model, params = make_params(CFG, dtype=jnp.bfloat16)
    loop = PagedServeLoop(model, params, max_batch=2, num_blocks=16,
                          block_size=8, chunk=16)
    args = (params, loop.pages, jnp.asarray(loop.bt),
            jnp.zeros((2, 1), jnp.int32), jnp.zeros((2, 1), jnp.int32))
    text = jax.jit(loop._decode_impl).lower(*args).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("attention/latent_write", "attention/mla_absorb",
                  "moe/router", "moe/routed_experts", "moe/shared_experts"):
        assert any(scope in n for n in names), scope


def test_serve_launcher_serves_moonlight_paged(capsys):
    """launch/serve.py passes --max-seq-len to the paged loop."""
    from repro.launch import serve
    serve.main(["--arch", "moonlight-16b-a3b", "--smoke", "--paged",
                "--batch", "2", "--prompt-len", "12", "--gen", "4",
                "--max-seq-len", "16"])
    out = capsys.readouterr().out
    assert "paged loop: 4 reqs, 16 tokens" in out
