"""Unified model interface: one `Model` object per architecture config.

Model exposes:
  param_defs()              -> pytree of ParamDef
  init(key)                 -> concrete params (CPU smoke / simulator tiers)
  apply(params, batch, mode, cache) -> (logits, aux_or_cache)
  cache_defs(batch, seq)    -> pytree of ParamDef for the decode KV/state cache
  input_defs(shape)         -> dict of ParamDef for every model input
  n_params / n_active_params -> ints (roofline MODEL_FLOPS terms)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax.numpy as jnp
import numpy as np

from repro.models import cnn, encdec, rglru, ssm, transformer
from repro.models.config import ModelConfig, ShapeConfig
from repro.models.param import (abstract_params, count_params, init_params,
                                is_def, pdef)


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    _defs: Callable
    _apply: Callable
    _cache_defs: Optional[Callable] = None

    # ---- params ----
    def param_defs(self):
        return self._defs(self.cfg)

    def abstract_params(self):
        return abstract_params(self.param_defs())

    def init(self, key):
        return init_params(key, self.param_defs())

    # ---- forward ----
    def apply(self, params, batch, *, mode="train", cache=None):
        return self._apply(params, self.cfg, batch, mode=mode, cache=cache)

    # ---- caches ----
    @property
    def supports_cache_spec(self) -> bool:
        """CacheSpec layouts (ring / int8) apply to growing KV caches;
        SSM / RG-LRU state, the enc-dec cross cache and MLA's latent rows
        keep their own conventions."""
        return self.cfg.family in ("dense", "moe", "vlm") and not self.cfg.mla

    def cache_defs(self, batch: int, seq_len: int, spec=None):
        """Decode-cache defs; `spec` (a models/cache.CacheSpec or its
        string form) overrides the config's cache_spec for transformer
        families, letting the layout policy probe candidate specs
        without rebuilding the model."""
        if self._cache_defs is None:
            raise ValueError(f"{self.cfg.name}: no decode cache (family="
                             f"{self.cfg.family})")
        if spec is not None and self.supports_cache_spec:
            return self._cache_defs(self.cfg, batch, seq_len, spec=spec)
        return self._cache_defs(self.cfg, batch, seq_len)

    @property
    def supports_paged_cache(self) -> bool:
        """Whether the model's cache defs can be paged: a decoder cache
        that grows a K/V or latent row per token and keeps every position.
        SSM/RG-LRU state is O(1) per sequence, the enc-dec cross cache is
        static and a sliding window keeps a ring, so those keep the
        contiguous path."""
        try:
            self.paged_cache_defs(1, 1, 1, 1)
        except ValueError:
            return False
        return True

    def paged_cache_defs(self, batch: int, num_blocks: int, block_size: int,
                         max_blocks_per_seq: int):
        """Raises ValueError, with the reason, for a cache that cannot be
        paged."""
        if self._cache_defs is not transformer.cache_defs:
            raise ValueError(f"{self.cfg.name}: paged KV cache unsupported "
                             f"(family={self.cfg.family}: its decode cache "
                             "does not grow a row per token)")
        return transformer.paged_cache_defs(
            self.cfg, batch, num_blocks, block_size, max_blocks_per_seq)

    # ---- inputs ----
    def input_defs(self, shape: ShapeConfig):
        cfg = self.cfg
        B = shape.global_batch
        if cfg.family == "cnn":
            return {
                "images": pdef((B, cfg.img_hw, cfg.img_hw, cfg.img_c),
                               ("batch", None, None, None), dtype=jnp.float32),
                "labels": pdef((B,), ("batch",), dtype=jnp.int32),
            }
        T = 1 if shape.kind == "decode" else shape.seq_len
        d: dict[str, Any] = {
            "tokens": pdef((B, T), ("batch", None), dtype=jnp.int32),
        }
        if shape.kind == "train":
            d["labels"] = pdef((B, T), ("batch", None), dtype=jnp.int32)
        if cfg.frontend == "vision_stub" and shape.kind != "decode":
            d["patch_embeds"] = pdef((B, cfg.frontend_len, cfg.d_model),
                                     ("batch", None, None),
                                     dtype=jnp.bfloat16)
        if cfg.is_encdec and shape.kind != "decode":
            el = encdec.enc_len_for(shape.seq_len)
            d["frames"] = pdef((B, el, cfg.d_model), ("batch", None, None),
                               dtype=jnp.bfloat16)
        if shape.kind == "decode":
            d["positions"] = pdef((B, 1), ("batch", None), dtype=jnp.int32)
        return d

    # ---- sizes (roofline) ----
    @property
    def n_params(self) -> int:
        return count_params(self.param_defs())

    @property
    def n_active_params(self) -> int:
        """Per-token active parameters (MoE: only k of E experts count)."""
        cfg = self.cfg
        if not cfg.num_experts:
            return self.n_params
        defs = self.param_defs()
        total = count_params(defs)
        moe = defs["layers"].get("moe")
        if moe is None:
            return total
        expert_leaves = [moe["w_gate"], moe["w_up"], moe["w_down"]]
        expert_total = sum(int(np.prod(l.shape)) for l in expert_leaves)
        active = expert_total * cfg.experts_per_token / cfg.num_experts
        return int(total - expert_total + active)


_FAMILY = {
    "dense": (transformer.lm_defs, transformer.lm_apply, transformer.cache_defs),
    "moe": (transformer.lm_defs, transformer.lm_apply, transformer.cache_defs),
    "vlm": (transformer.lm_defs, transformer.lm_apply, transformer.cache_defs),
    "ssm": (ssm.ssm_lm_defs, ssm.ssm_lm_apply, ssm.ssm_cache_defs),
    "hybrid": (rglru.hybrid_lm_defs, rglru.hybrid_lm_apply,
               rglru.hybrid_cache_defs),
    "audio": (encdec.encdec_defs, encdec.encdec_apply, encdec.encdec_cache_defs),
    "cnn": (cnn.cnn_defs, cnn.cnn_apply, None),
    "mlp": (cnn.mlp_classifier_defs, cnn.mlp_classifier_apply, None),
}


def build_model(cfg: ModelConfig) -> Model:
    fam = "mlp" if (cfg.family == "cnn" and not cfg.cnn_channels
                    and cfg.d_model) else cfg.family
    defs, apply, cache = _FAMILY[fam]
    return Model(cfg, defs, apply, cache)
