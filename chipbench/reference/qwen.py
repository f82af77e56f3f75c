"""Plain float32 forward of a Qwen1.5 decoder (Qwen/Qwen1.5-4B's equations:
RMSNorm, rotary attention with QKV bias, SiLU-gated MLP, untied head),
written from the published description in jax.numpy.  No kernel, cache,
batching or paging; matmuls at `highest` precision.  It imports nothing of
the program and reads the weights the benchmark made, by name.

Layers run one at a time under `lax.scan`, each layer's bf16 weights cast
to float32 inside the step, so only one layer is ever held in float32.

`precision="fp8"` is the control: every matmul input (weights and
activations) is rounded to float8 e4m3 with one scale per tensor, the
step below the bfloat16 the configuration states."""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

LAYER_KEYS = ("ln1", "ln2", "wq", "wk", "wv", "wo", "bq", "bk", "bv",
              "w_up", "w_gate", "w_down")
F8_MAX = 448.0


def _fp8(x):
    """Round to float8 e4m3 with one per-tensor scale, back to float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


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


def _layer(x, w, pos, c, q8):
    """One decoder layer over the whole sequence x (T, d), causal."""
    f32 = lambda a: a.astype(jnp.float32)
    mm = (lambda a: _fp8(f32(a))) if q8 else f32
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    h = mm(_rmsnorm(x, f32(w["ln1"]), eps))
    q = jnp.einsum("td,dhk->thk", h, mm(w["wq"])) + f32(w["bq"])
    k = jnp.einsum("td,dhk->thk", h, mm(w["wk"])) + f32(w["bk"])
    v = jnp.einsum("td,dhk->thk", h, mm(w["wv"])) + f32(w["bv"])
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    G = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    s = jnp.einsum("thk,shk->hts", q, k) / math.sqrt(q.shape[-1])
    T = x.shape[0]
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("hts,shk->thk", p, v)
    x = x + jnp.einsum("thk,hkd->td", mm(a), mm(w["wo"]))
    h = mm(_rmsnorm(x, f32(w["ln2"]), eps))
    g = jax.nn.silu(h @ mm(w["w_gate"])) * (h @ mm(w["w_up"]))
    return x + mm(g) @ mm(w["w_down"])


def flat_layers(weights) -> dict:
    """The stacked (L, ...) layer leaves of the program-shaped tree."""
    lw = weights["layers"]
    return {"ln1": lw["ln1"]["scale"], "ln2": lw["ln2"]["scale"],
            **{k: lw["attn"][k] for k in ("wq", "wk", "wv", "wo",
                                          "bq", "bk", "bv")},
            **{k: lw["mlp"][k] for k in ("w_up", "w_gate", "w_down")}}


@partial(jax.jit, static_argnames=("c", "precision"))
def logits_at(weights, tokens, rows, *, c, precision="fp32"):
    """float32 logits (len(rows), V) at positions `rows` of `tokens` (T,)
    (row i predicts tokens[rows[i] + 1]).  `c` is a hashable tuple of the
    configuration's (key, value) pairs."""
    c = dict(c)
    q8 = precision == "fp8"
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(tokens.shape[0])
        x = weights["embed"]["tok"][tokens].astype(jnp.float32)

        def step(x, w):
            return _layer(x, w, pos, c, q8), None

        x, _ = lax.scan(step, x, flat_layers(weights))
        h = _rmsnorm(x[rows], weights["final_norm"]["scale"].astype(
            jnp.float32), c["rms_norm_eps"])
        head = weights["embed"].get("unembed")
        head = weights["embed"]["tok"].T if head is None else head
        head = head.astype(jnp.float32)
        if q8:
            h, head = _fp8(h), _fp8(head)
        return h @ head


def gaps(logits, tokens):
    """How far each chosen token's logit lies below the row's best."""
    chosen = jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]
    return logits.max(axis=1) - chosen
