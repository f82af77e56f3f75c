"""Plain float32 forward of Moonlight-16B-A3B (deepseek_v3 blocks:
arXiv:2412.19437, with MLA from arXiv:2405.04434), written from the
published equations in jax.numpy.  No kernel, cache, batching or paging;
matmuls at `highest` precision.  It imports nothing of the program and
reads the weights the benchmark made, by the program's leaf names.

A layer: x += MLA(rmsnorm(x)); x += FFN(rmsnorm(x)), RMSNorm eps from the
configuration.  MLA, expanded: q = h Wq split into q_nope (128) and q_pe
(64, rotated); c = rmsnorm(h W_DKV) (kv_lora_rank 512, its own norm) and
k_pe = rope(h W_KR) (64, one for all heads); per head k_nope = c W_UK,
v = c W_UV; scores (q_nope.k_nope + q_pe.k_pe) / sqrt(192), causal
softmax, out = p v W_O.  FFN: layer 0 a SiLU-gated MLP; the rest a MoE:
sigmoid router scores s = sigmoid(h W_r) over all E experts, the top k of
s + e_score_correction_bias chosen, each weighted by its unbiased s,
normalised over the k and scaled by routed_scaling_factor (noaux_tc with
one group); the routed experts' SiLU-gated MLPs summed with those
weights, plus the shared experts' MLP.

Departures, each applied to the program alike:
  * RoPE pairs lane i with lane i + 32 of the 64 rope lanes (rotate-half);
    the published code pairs neighbours.  That is a fixed permutation of
    the rope lanes of W_q and W_KR, and the weights are random.
  * wkv_b is held as its two halves, W_UK and W_UV.
  * Only the held experts (the weights' expert axis, experts
    `held_first`.. of the router's E) are computed: a choice of an expert
    held on another chip adds nothing here, as in the program, which is
    what one chip of an expert-parallel deployment adds to the result.

Layers run one at a time under `lax.scan`, each layer's bf16 weights cast
to float32 inside the step, so only one layer is ever held in float32.

`precision="fp8"` is the control: every matmul input (weights and
activations) is rounded to float8 e4m3 with one scale per tensor, the
step below the bfloat16 the configuration states.  `precision="bf16"`
rounds every matmul input to bfloat16 instead, as the program's inputs
are: its expert choices against float32's count routing flips.
`precision="bf16_stream"` also rounds the residual stream to bfloat16
after every layer's two additions, as the program holds it: a witness of
how wide bfloat16 alone makes the widest gaps."""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

F8_MAX = 448.0


def _fp8(x):
    """Round to float8 e4m3 with one per-tensor scale, back to float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rounder(precision):
    f32 = lambda a: a.astype(jnp.float32)
    if precision == "fp8":
        return lambda a: _fp8(f32(a))
    if precision == "bf16":
        return lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    return f32


def _rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """Rotate-half RoPE: x (T, H, D), pos (T,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _mlp(h, w, mm):
    g = jax.nn.silu(h @ mm(w["w_gate"])) * (h @ mm(w["w_up"]))
    return mm(g) @ mm(w["w_down"])


def _mla(x, w, pos, c, mm):
    """x += expanded latent attention over the whole sequence, causal."""
    f32 = lambda a: a.astype(jnp.float32)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    r, dn = c["kv_lora_rank"], c["qk_nope_head_dim"]
    h = mm(_rmsnorm(x, f32(w["ln1"]), eps))
    q = jnp.einsum("td,dhk->thk", h, mm(w["wq"]))
    q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], pos, theta)
    kv = h @ mm(w["wkv_a"])
    lat = mm(_rmsnorm(kv[:, :r], f32(w["kv_norm"]), eps))
    k_pe = _rope(kv[:, None, r:], pos, theta)                 # (T, 1, 64)
    k_nope = jnp.einsum("tr,rhk->thk", lat, mm(w["w_uk"]))
    v = jnp.einsum("tr,rhk->thk", lat, mm(w["w_uv"]))
    s = (jnp.einsum("thk,shk->hts", mm(q_nope), mm(k_nope))
         + jnp.einsum("thk,sk->hts", mm(q_pe), mm(k_pe[:, 0])))
    s = s / math.sqrt(q.shape[-1])
    T = x.shape[0]
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("hts,shk->thk", mm(p), mm(v))
    return x + jnp.einsum("thk,hkd->td", mm(a), mm(w["wo"]))


def route(h, w, c, mm):
    """(weights (T, E) with zeros off the chosen experts, chosen ids (T, k)
    sorted) of the sigmoid router."""
    k = c["num_experts_per_tok"]
    s = jax.nn.sigmoid(h @ mm(w["w_router"]))
    _, idx = lax.top_k(s + w["e_bias"].astype(jnp.float32), k)
    chosen = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(1.0)
    wt = s * chosen
    wt = wt / (wt.sum(-1, keepdims=True) + 1e-20) * c["routed_scaling_factor"]
    return wt, jnp.sort(idx, axis=-1)


def _moe(h, w, c, mm, held_first):
    """Held routed experts, densely weighted, plus the shared experts."""
    wt, idx = route(h, w, c, mm)
    Eh = w["w_gate"].shape[0]
    wt = lax.dynamic_slice_in_dim(wt, held_first, Eh, axis=1)   # (T, Eh)
    g = jnp.einsum("td,edf->tef", h, mm(w["w_gate"]))
    u = jnp.einsum("td,edf->tef", h, mm(w["w_up"]))
    y = jnp.einsum("tef,efd->ted", mm(jax.nn.silu(g) * u), mm(w["w_down"]))
    return jnp.einsum("ted,te->td", y, wt) + _mlp(h, w["shared"], mm), idx


def _post(x, w, c, mm):
    return mm(_rmsnorm(x, w["ln2"].astype(jnp.float32), c["rms_norm_eps"]))


def flat_layers(stack) -> dict:
    """The stacked (L, ...) leaves of one of the program's layer stacks."""
    out = {"ln1": stack["ln1"]["scale"], "ln2": stack["ln2"]["scale"],
           "kv_norm": stack["attn"]["kv_norm"]["scale"],
           **{k: stack["attn"][k]
              for k in ("wq", "wkv_a", "w_uk", "w_uv", "wo")}}
    if "mlp" in stack:
        out.update(stack["mlp"])
    else:
        out.update({k: v for k, v in stack["moe"].items()})
    return out


@partial(jax.jit, static_argnames=("c", "precision", "held_first"))
def forward(weights, tokens, rows, *, c, precision="fp32", held_first=0):
    """(float32 logits (len(rows), V) at positions `rows` of `tokens` (T,)
    -- row i predicts tokens[rows[i] + 1] --, the MoE layers' chosen
    experts at those rows (L_moe, len(rows), k)).  `c` is a hashable tuple
    of the configuration's (key, value) pairs."""
    c = dict(c)
    stream = precision == "bf16_stream"
    mm = _rounder("bf16" if stream else precision)
    res = mm if stream else (lambda a: a)
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(tokens.shape[0])
        x = weights["embed"]["tok"][tokens].astype(jnp.float32)

        def dense(x, w):
            x = res(_mla(x, w, pos, c, mm))
            return res(x + _mlp(_post(x, w, c, mm), w, mm)), None

        def moe(x, w):
            x = res(_mla(x, w, pos, c, mm))
            y, idx = _moe(_post(x, w, c, mm), w, c, mm, held_first)
            return res(x + y), idx[rows]

        x, _ = lax.scan(dense, x, flat_layers(weights["dense_layers"]))
        x, routes = lax.scan(moe, x, flat_layers(weights["layers"]))
        h = _rmsnorm(x[rows], weights["final_norm"]["scale"].astype(
            jnp.float32), c["rms_norm_eps"])
        head = weights["embed"]["unembed"].astype(jnp.float32)
        return mm(h) @ mm(head), routes


def logits_at(weights, tokens, rows, *, c, precision="fp32", held_first=0):
    """forward's logits alone."""
    return forward(weights, tokens, rows, c=c, precision=precision,
                   held_first=held_first)[0]


def gaps(logits, tokens):
    """How far each chosen token's logit lies below the row's best."""
    chosen = jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]
    return logits.max(axis=1) - chosen
