"""CacheSpec: THE one place the KV-cache convention lives.

Every question about the decode cache -- what leaves it has, their
shapes, dtypes and logical sharding axes, how many bytes it costs on a
mesh, how a prefill packs one and how a decode step writes one -- is
answered here.  models/transformer.py, models/model_factory.py,
models/layers.py, dist/policy.py and launch/dryrun.py all used to carry
their own copy of this convention; they now delegate.

A CacheSpec is `layout[:shards]/dtype`:

  layout  "replicated" -- no head or seq sharding (the old silent
                          fallback, now an explicit choice);
          "head"       -- kv heads shard over "model" (canonical TP
                          decode; silently == replicated when
                          kv_heads %% model != 0, which resolve() turns
                          into an explicit ring fallback);
          "ring"       -- the SEQUENCE dim shards over "model" (context
                          parallelism): each shard owns S/n cache slots
                          and decode merges per-segment softmax partials
                          via log-sum-exp (layers.ring_decode_attention).
                          Always divides (seq lengths are 2^k), so it is
                          the fallback when head-sharding can't.
  shards  ring only: the static segment count; 0 = the ambient mesh's
          "model" axis size at trace time.
  dtype   "bf16", or "int8" -- rowwise-quantised K/V (kernels/quant8,
          per (token, head) fp32 scales over head_dim) with dequant
          fused into the attention reads by XLA.  Halves cache HBM
          (+ ~3%% scale overhead) at a logit cost bounded by
          INT8_LOGIT_BOUND (tests/test_cache_spec.py pins the parity).

The spec is owned by the model config (`ModelConfig.cache_spec`, default
"auto" == "head/bf16" == the historical behaviour); the serve policy
(dist/policy.py) scores (weight layout x cache spec) products and
launchers thread the winning spec back in via
`dataclasses.replace(cfg, cache_spec=...)`.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.models.param import pdef

CACHE_LAYOUTS = ("replicated", "head", "ring")
CACHE_DTYPES = ("bf16", "int8")

#: decode headroom appended to non-windowed prefill caches (slots for
#: subsequently generated tokens).  Historically lived in models/layers.
PREFILL_DECODE_MARGIN = 128


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    layout: str = "head"
    dtype: str = "bf16"
    shards: int = 0          # ring segment count; 0 = ambient "model" size

    def __post_init__(self):
        if self.layout not in CACHE_LAYOUTS:
            raise ValueError(f"unknown cache layout '{self.layout}'; "
                             f"known: {CACHE_LAYOUTS}")
        if self.dtype not in CACHE_DTYPES:
            raise ValueError(f"unknown cache dtype '{self.dtype}'; "
                             f"known: {CACHE_DTYPES}")
        if self.shards and self.layout != "ring":
            raise ValueError("shards only applies to the ring layout")

    @property
    def quantized(self) -> bool:
        return self.dtype == "int8"

    @property
    def name(self) -> str:
        s = f":{self.shards}" if self.shards else ""
        return f"{self.layout}{s}/{self.dtype}"

    @classmethod
    def parse(cls, s) -> "CacheSpec":
        """"auto" | "layout[:shards]/dtype" | CacheSpec (passthrough)."""
        if isinstance(s, cls):
            return s
        if s is None or s == "auto":
            return cls()
        layout, _, dtype = str(s).partition("/")
        layout, _, shards = layout.partition(":")
        return cls(layout=layout, dtype=dtype or "bf16",
                   shards=int(shards) if shards else 0)


def spec_of(cfg) -> CacheSpec:
    """The model config's cache spec (ModelConfig.cache_spec string)."""
    return CacheSpec.parse(getattr(cfg, "cache_spec", "auto"))


# ---------------------------------------------------------------------------
# Logical axes + abstract leaves
# ---------------------------------------------------------------------------

def kv_axes(spec: CacheSpec):
    """Logical axes of one (batch, seq, kv_heads, head_dim) cache leaf.

    ring puts an EXPLICIT ("model",) tuple on the seq dim: explicit
    tuples bind in resolution pass 0 (dist/sharding.py), so "model" is
    claimed before the kv_heads priority wave can take it and the heads
    dim falls back to replicated -- exactly the ring contract.
    """
    if spec.layout == "ring":
        return ("batch", ("model",), "kv_heads", None)
    if spec.layout == "replicated":
        return ("batch", "kv_seq", None, None)
    return ("batch", "kv_seq", "kv_heads", None)


def ring_segments(spec: CacheSpec, seq_len: int) -> int:
    """Static ring segment count for a cache of `seq_len` slots: the
    spec's shard count (ambient "model" size when unset), reduced to the
    largest power-of-two divisor of seq_len so no slot padding is ever
    needed (padded slots would need masking against uninitialised keys).
    """
    if spec.layout != "ring":
        return 1
    from repro.dist.sharding import mesh_axis_size
    n = spec.shards or mesh_axis_size("model")
    while n > 1 and seq_len % n:
        n //= 2
    return max(n, 1)


def attention_cache_defs(cfg, batch: int, seq_len: int,
                         spec: CacheSpec | str | None = None):
    """Abstract KV-cache leaves (per layer) under a CacheSpec.

    bf16: {k, v, len}; int8 adds per-(token, head) fp32 scales
    {k_scale, v_scale} over the head_dim axis (rowwise layout of
    kernels/quant8: q keeps the cache's shape and therefore its
    sharding).
    """
    spec = CacheSpec.parse(spec) if spec is not None else spec_of(cfg)
    keep = min(cfg.window, seq_len) if cfg.window else seq_len
    ax = kv_axes(spec)
    kv = (batch, keep, cfg.num_kv_heads, cfg.head_dim)
    kv_dtype = jnp.int8 if spec.quantized else jnp.bfloat16
    d = {
        "k": pdef(kv, ax, dtype=kv_dtype, init="zeros"),
        "v": pdef(kv, ax, dtype=kv_dtype, init="zeros"),
        "len": pdef((batch,), ("batch",), dtype=jnp.int32, init="zeros"),
    }
    if spec.quantized:
        sc = (batch, keep, cfg.num_kv_heads, 1)
        d["k_scale"] = pdef(sc, ax, dtype=jnp.float32, init="zeros")
        d["v_scale"] = pdef(sc, ax, dtype=jnp.float32, init="zeros")
    return d


def paged_attention_cache_defs(cfg, num_blocks, block_size):
    """Abstract paged-cache leaves (per layer): the K/V block POOL shared
    by ALL sequences, and nothing else -- the block tables and positions
    arrive with each step.  Unlike the contiguous cache, HBM scales with
    the pool (total tokens resident), not max_batch * max_len.  A block
    is head-major, (Hkv, BS, D): each head's BS rows are whole bf16
    tiles, so the pool is stored unpadded and in the order the decode
    kernel reads it.  The pool is bf16 + head-sharded only (the block dim
    hosts scatter writes, which GSPMD cannot shard without gathering the
    pool)."""
    kv = (num_blocks, cfg.num_kv_heads, block_size, cfg.head_dim)
    ax = (None, "kv_heads", None, None)
    return {
        "kp": pdef(kv, ax, dtype=jnp.bfloat16, init="zeros"),
        "vp": pdef(kv, ax, dtype=jnp.bfloat16, init="zeros"),
    }


#: lanes of a TPU vector register: a latent row is stored padded to a
#: multiple of this, so that a pool block is whole lane tiles
LANES = 128


def latent_width(cfg) -> int:
    """Values in a latent row: c_kv (kv_lora_rank) || k_pe (rope dims)."""
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def latent_lanes(cfg) -> int:
    """The row as stored: latent_width padded with zeros to whole lane
    tiles (Moonlight: 512 + 64 = 576 values in 640 lanes)."""
    return -(-latent_width(cfg) // LANES) * LANES


def latent_cache_defs(cfg, batch: int, seq_len: int):
    """MLA's contiguous cache, per layer: one latent row per position.
    The layout policy sizes it; MLA decodes only through the paged pool."""
    return {
        "latent": pdef((batch, seq_len, latent_width(cfg)),
                       ("batch", "kv_seq", None), dtype=jnp.bfloat16,
                       init="zeros"),
        "len": pdef((batch,), ("batch",), dtype=jnp.int32, init="zeros"),
    }


def paged_latent_cache_defs(cfg, num_blocks, block_size):
    """MLA's paged cache, per layer: a pool of latent rows (NB, BS,
    latent_lanes) shared by all slots -- one row per token, read by every
    head."""
    return {
        "latent": pdef((num_blocks, block_size, latent_lanes(cfg)),
                       (None, None, None), dtype=jnp.bfloat16, init="zeros"),
    }


# ---------------------------------------------------------------------------
# Mesh resolution (policy-side): which specs are real on a given mesh
# ---------------------------------------------------------------------------

def resolve(spec: CacheSpec | str, cfg, mesh) -> tuple[CacheSpec, str]:
    """Effective spec on `mesh` + a note when the request degrades.

    "head" with kv_heads %% model != 0 cannot head-shard; the resolver
    reports it (the old code replicated ~100 GB/dev silently -- see
    dist/sharding.ShardingFallbackWarning) and callers offer "ring"
    as the candidate that always divides.
    """
    spec = CacheSpec.parse(spec)
    sizes = {str(k): int(v) for k, v in dict(mesh.shape).items()}
    m = sizes.get("model", 1)
    if spec.layout == "head" and m > 1 and cfg.num_kv_heads % m:
        return spec, (f"kv_heads={cfg.num_kv_heads} % model={m} != 0: "
                      f"head layout degrades to replicated ({m}-way "
                      f"replication of the cache); use ring")
    if spec.layout == "ring":
        n = spec.shards or m
        if n <= 1:
            return spec, "ring with a 1-wide model axis == replicated"
    return spec, ""


def cache_bytes(cfg, batch: int, seq_len: int,
                spec: CacheSpec | str | None, mesh, rules=None,
                num_layers: int | None = None) -> float:
    """Analytic per-device cache bytes for a spec on a mesh: the leaf
    defs resolved through the sharding rules, summed over layers.  This
    is the number dist/policy.py scores (weight x cache) products with
    and launch/dryrun.py records as `cache_bytes_analytic`."""
    from repro.dist.policy import sharded_bytes
    per_layer = attention_cache_defs(cfg, batch, seq_len, spec)
    L = num_layers if num_layers is not None else cfg.num_layers
    return sharded_bytes(per_layer, mesh, rules) * L


# ---------------------------------------------------------------------------
# Quantised read/write (rowwise int8 over head_dim; kernels/quant8)
# ---------------------------------------------------------------------------

#: Scale-relative max logit error (max|d| / max|ref|) of teacher-forced
#: decode allowed for an int8 cache against bf16.  Set from readings, not
#: derived: int8 measured 0.0102 at smoke depth (2 layers, CPU) and 0.0165
#: at qwen1.5-4b's 40 layers (TPU v5e); the bf16 cache rounded rowwise to
#: int4 levels, the control that must fail it, measured 0.046 at smoke
#: depth (tests/test_cache_spec.py) and is read at full depth by
#: chip_smoke.py.
INT8_LOGIT_BOUND = 0.03


def _q8_impl() -> str:
    # the Pallas rowwise kernel on TPU; the jnp reference elsewhere
    # (pallas_call is opaque to GSPMD partitioning, so SPMD CPU dryruns
    # must trace the pure-jnp path)
    return "auto" if jax.default_backend() == "tpu" else "ref"


def quantize_kv(x):
    """(..., D) bf16 -> ((...,D) int8, (...,1) fp32 scales)."""
    from repro.kernels.quant8 import ops
    return ops.quantize_rowwise(x, impl=_q8_impl())


def dequantize_kv(q, scale, out_dtype=jnp.bfloat16):
    """Inverse of quantize_kv.  XLA fuses the convert+scale into the
    attention einsum that consumes it, so the bf16 cache never
    materialises in HBM on the fused path."""
    from repro.kernels.quant8 import ops
    return ops.dequantize_rowwise(q, scale, out_dtype=out_dtype,
                                  impl=_q8_impl())


def read_kv(cache):
    """Cache leaves -> (k, v) bf16 views (dequantised when int8)."""
    if "k_scale" in cache:
        return (dequantize_kv(cache["k"], cache["k_scale"]),
                dequantize_kv(cache["v"], cache["v_scale"]))
    return cache["k"], cache["v"]


# ---------------------------------------------------------------------------
# Prefill pack + decode write (the two places a cache is produced)
# ---------------------------------------------------------------------------

def _pad_seq(x, target):
    pad = target - x.shape[1]
    if pad <= 0:
        return x
    return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))


def pack_prefill_cache(cfg, kk, vv, *, window: int,
                       spec: CacheSpec | None = None):
    """Pack full-sequence K/V (B, T, Hkv, D) into a fresh decode cache.

    window: ring-buffer trim to the last `window` positions (decode
    overwrites slot len %% window); else pad PREFILL_DECODE_MARGIN slots
    of decode headroom, rounded up so ring segment counts divide.
    """
    spec = spec or spec_of(cfg)
    B, T = kk.shape[0], kk.shape[1]
    if window and T >= window:
        kk, vv = kk[:, -window:], vv[:, -window:]
        keep = window
    else:
        keep = T + PREFILL_DECODE_MARGIN
        n = spec.shards if spec.layout == "ring" else 0
        if n:
            keep = -(-keep // n) * n
    cache = {"len": jnp.full((B,), T, jnp.int32)}
    if spec.quantized:
        kq, ks = quantize_kv(kk)
        vq, vs = quantize_kv(vv)
        cache.update(k=_pad_seq(kq, keep), v=_pad_seq(vq, keep),
                     k_scale=_pad_seq(ks, keep), v_scale=_pad_seq(vs, keep))
    else:
        cache.update(k=_pad_seq(kk, keep), v=_pad_seq(vv, keep))
    return constrain_cache(cache, spec)


def write_kv(cache, kk, vv, slots, *, spec: CacheSpec):
    """Write K/V rows (B, C, Hkv, D) at per-batch `slots` (vmapped
    dynamic_update_slice: sequences at different positions coexist in
    one batch -- continuous batching).  C == 1 for decode steps; chunked
    prefill writes whole chunks.  Quantisation follows the CACHE's own
    leaves (an int8 cache carries k_scale/v_scale), so a bf16 cache
    built before a spec change still round-trips."""
    upd = jax.vmap(
        lambda c, u, s: jax.lax.dynamic_update_slice_in_dim(c, u, s, 0))
    out = dict(cache)
    if "k_scale" in cache:
        kq, ks = quantize_kv(kk)
        vq, vs = quantize_kv(vv)
        out["k"] = upd(cache["k"], kq.astype(cache["k"].dtype), slots)
        out["v"] = upd(cache["v"], vq.astype(cache["v"].dtype), slots)
        out["k_scale"] = upd(cache["k_scale"], ks, slots)
        out["v_scale"] = upd(cache["v_scale"], vs, slots)
    else:
        out["k"] = upd(cache["k"], kk.astype(cache["k"].dtype), slots)
        out["v"] = upd(cache["v"], vv.astype(cache["v"].dtype), slots)
    return constrain_cache(out, spec)


def constrain_cache(cache, spec: CacheSpec | str | None):
    """Re-assert the spec's sharding on freshly written cache leaves."""
    from repro.dist.sharding import constrain
    spec = CacheSpec.parse(spec) if spec is not None else CacheSpec()
    ax = kv_axes(spec)
    out = dict(cache)
    for key in ("k", "v", "k_scale", "v_scale"):
        if key in out:
            out[key] = constrain(out[key], ax)
    return out
