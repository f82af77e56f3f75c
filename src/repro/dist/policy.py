"""Memory-aware serve-layout policy: pick weight placement from measured HBM.

FLight's resource manager places FL work on heterogeneous workers using
cheap heuristics over measured capacity (paper SSIII-B).  This module is
the serving analogue for the SPMD stack: per (arch x shape x mesh) cell it
picks HOW weights are laid out across the mesh from the program's own
memory numbers, replacing the hardcoded `n_params * 2 / TP < 8 GB` check
that used to live in launch/dryrun.py.

Candidate layouts (dist/sharding.py::SERVE_LAYOUTS, most stationary
first):

  stationary -- SERVE_RULES: weights tensor-parallel over "model" only,
                replicated over "data"; zero weight traffic per step.
  hybrid     -- HYBRID_SERVE_RULES: body weights stationary, but the
                embedding / lm_head tables (logical "vocab"/"embed" dims)
                also shard over "data"; for models whose body fits but
                whose vocab tables blow the budget.
  fsdp       -- DEFAULT_RULES: fully-sharded weights (the training
                layout); always fits, pays weight all-gathers per step.

Since the CacheSpec layer (models/cache.py) the policy scores the full
(weight layout x cache spec) PRODUCT for serve cells: each weight layout
is paired with every CACHE_SPEC_CANDIDATES entry (head/bf16, ring/bf16,
head/int8, ring/int8), plus chunked-prefill variants for long-prompt
prefill cells.  int8 cache reads are charged at bf16-equivalent bytes in
the step-time proxy, so quantization is a FIT tool (smaller residency)
rather than a modeled speed win, and the historical head/bf16 convention
wins whenever it fits.

Decision procedure (`decide`): every candidate gets a CandidateEval with
predicted peak per-device HBM and predicted step time.  A candidate is
FEASIBLE when `hbm_bytes <= DEVICE_HBM_BYTES * margin` (margin defaults to
0.9: leave 10% headroom for allocator fragmentation + collective
scratch).  Among feasible candidates the fastest predicted step wins
(ties: the more stationary layout, i.e. earlier in SERVE_LAYOUTS order).
If NOTHING fits -- the huge-MoE case -- the policy falls back to the
candidate with the smallest peak (fsdp in practice) and flags
`fits=False`.

Evaluators (where the numbers come from):

  * eval_from_compiled(...)  -- XLA ground truth: `memory_analysis` of an
    AOT-compiled program (launch/dryrun.py compiles every candidate and
    caches the probes in the artifact JSON), step time from the
    trip-count-aware hlo_cost roofline.
  * analytic_eval(...)       -- no compile: exact per-device param / cache
    / input bytes from the ParamDef tree resolved through the candidate's
    RuleSet, plus an activation-workspace term; used by launch/serve.py
    and ServeLoop where compiling three layouts first is not acceptable.

EXPERIMENTS.md ("Layout policy decisions") tabulates the chosen layout and
headroom for every cell of the committed dryrun sweep.
"""
from __future__ import annotations

import dataclasses
import math

from repro.dist.sharding import (SERVE_LAYOUTS, logical_to_mesh_spec,
                                 serve_layout_rules)

#: Per-device HBM of the modeled chip (v5e-class, 16 GB; see the hardware
#: constants in dist/hlo_analysis.py): the budget of CPU AOT dry-runs,
#: which compile for a chip that is not attached.
DEVICE_HBM_BYTES = 16e9

#: Fraction of DEVICE_HBM_BYTES a layout may use before it is infeasible.
DEFAULT_MARGIN = 0.9


def device_hbm_bytes(device) -> float:
    """The HBM budget to lay a model out on `device`.

    On the CPU backend (AOT dry-runs, tests) the modeled v5e constant; on
    an attached accelerator the runtime's own limit,
    `memory_stats()["bytes_limit"]`.  An accelerator that does not report
    one cannot be sized, and that is an error, never a default."""
    if device.platform == "cpu":
        return DEVICE_HBM_BYTES
    stats = device.memory_stats() or {}
    if "bytes_limit" not in stats:
        raise ValueError(f"cannot size the memory of {device.platform} "
                         f"device_kind={device.device_kind!r}: "
                         "memory_stats() reports no bytes_limit")
    return float(stats["bytes_limit"])


# ---------------------------------------------------------------------------
# Evaluations
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CandidateEval:
    """Predicted peak HBM + step time for one (weight layout x cache
    spec) candidate.  `cache` is a models/cache.CacheSpec name
    ("ring/int8", ...; "" = the model's default spec / no cache);
    `chunked` marks the chunked-prefill variant that streams a long
    prompt through bounded chunks instead of one-shot prefill."""
    layout: str
    hbm_bytes: float          # peak per-device HBM the program needs
    step_time_s: float        # predicted step time (roofline bound)
    source: str = "analytic"  # "xla" (compiled memory_analysis) | "analytic"
    detail: dict = dataclasses.field(default_factory=dict)
    cache: str = ""
    chunked: bool = False

    @property
    def key(self) -> str:
        """Unique candidate id: layout[+cache][+chunked]."""
        k = self.layout
        if self.cache:
            k += f"+{self.cache}"
        if self.chunked:
            k += "+chunked"
        return k

    def as_dict(self) -> dict:
        return {"layout": self.layout, "hbm_bytes": self.hbm_bytes,
                "hbm_gb": round(self.hbm_bytes / 1e9, 3),
                "step_time_s": self.step_time_s, "source": self.source,
                **({"cache": self.cache} if self.cache else {}),
                **({"chunked": True} if self.chunked else {}),
                **({"detail": self.detail} if self.detail else {})}


def peak_hbm_bytes(memory_analysis: dict) -> float:
    """Peak per-device HBM from an XLA `memory_analysis` dict.

    arguments + temporaries + the non-aliased slice of the outputs
    (donated/aliased outputs live in their argument's buffer).
    """
    args = memory_analysis.get("argument_size_in_bytes", 0)
    temp = memory_analysis.get("temp_size_in_bytes", 0)
    out = memory_analysis.get("output_size_in_bytes", 0)
    alias = memory_analysis.get("alias_size_in_bytes", 0)
    return float(args + temp + max(out - alias, 0))


def eval_from_compiled(layout: str, memory_analysis: dict,
                       roofline: dict, *, cache: str = "",
                       chunked: bool = False) -> CandidateEval:
    """CandidateEval from dryrun-grade numbers (XLA memory_analysis +
    hlo_cost roofline dict with a `bound_s` key)."""
    return CandidateEval(
        layout=layout,
        hbm_bytes=peak_hbm_bytes(memory_analysis),
        step_time_s=float(roofline.get("bound_s", 0.0)),
        source="xla",
        detail={"memory_analysis": dict(memory_analysis)},
        cache=cache, chunked=chunked)


# ---------------------------------------------------------------------------
# Analytic evaluator (no compile)
# ---------------------------------------------------------------------------

def _def_leaves(defs):
    import jax
    from repro.models.param import is_def
    return jax.tree.leaves(defs, is_leaf=is_def)


def sharded_bytes(defs, mesh, rules) -> float:
    """Exact per-device bytes of a ParamDef tree laid out under `rules`."""
    sizes = {str(k): int(v) for k, v in dict(mesh.shape).items()}
    total = 0.0
    for d in _def_leaves(defs):
        spec = logical_to_mesh_spec(d.logical_axes, d.shape, mesh, rules)
        shard = 1
        for entry in spec:
            if entry is None:
                continue
            for ax in (entry if isinstance(entry, tuple) else (entry,)):
                shard *= sizes.get(ax, 1)
        total += (d.dtype.itemsize * math.prod(d.shape)) / shard
    return total


#: Tokens per chunk of the chunked-prefill variant (matches the chunk
#: size launch/dryrun.py compiles for long-prompt cells).
CHUNK_TOKENS = 4096

#: CacheSpec candidates the serve policy sweeps per weight layout, in
#: preference order: the historical head-sharded bf16 convention first,
#: then seq-sharded ring, then the int8 variants (a FIT tool, not a
#: modeled speed win -- int8 cache reads are charged at bf16-equivalent
#: bytes so bf16 wins whenever both fit).
CACHE_SPEC_CANDIDATES = ("head/bf16", "ring/bf16", "head/int8", "ring/int8")


def _cache_bytes(model, shape, mesh, rules, cache_spec):
    """(resident_bytes, stream_bytes) of the decode/prefill cache under
    `cache_spec` ("" / None = the model's config default).  stream_bytes
    is what the attention must move per step, charged at bf16 width even
    for int8 caches (dequant runs at full width in-register; quantizing
    shrinks RESIDENCY, which is the fit story, not arithmetic traffic)."""
    if shape.kind not in ("decode", "prefill") or model._cache_defs is None:
        return 0.0, 0.0
    B, S = shape.global_batch, shape.seq_len
    if cache_spec and model.supports_cache_spec:
        from repro.models.cache import CacheSpec
        spec = CacheSpec.parse(cache_spec)
        resident = sharded_bytes(model.cache_defs(B, S, spec=spec),
                                 mesh, rules)
        if spec.quantized:
            bf16 = dataclasses.replace(spec, dtype="bf16")
            stream = sharded_bytes(model.cache_defs(B, S, spec=bf16),
                                   mesh, rules)
        else:
            stream = resident
        return resident, stream
    resident = sharded_bytes(model.cache_defs(B, S), mesh, rules)
    return resident, resident


def analytic_eval(model, shape, mesh, layout: str, *,
                  cache_spec: str | None = None, chunked: bool = False,
                  hbm_bw: float | None = None) -> CandidateEval:
    """Compile-free CandidateEval: param/cache/input bytes from the
    ParamDef tree resolved through the (layout, cache_spec) candidate's
    RuleSet, plus a 2-deep activation workspace, with a
    weight/cache-streaming step-time proxy.

    The step-time proxy charges every byte the device must READ each step
    (stationary weights stream from local HBM; fsdp weights must first be
    gathered -- charged at ICI bandwidth, which is what makes stationary
    win whenever it fits).  Prefill counts the produced cache against
    peak too: the one-shot prefill entry RETURNS the cache, and outputs
    don't alias any argument there.  `chunked` models the chunked-prefill
    variant: peak activations shrink to one CHUNK_TOKENS chunk, but the
    weights stream once per chunk, so one-shot prefill stays preferred
    whenever it fits.
    """
    from repro.dist.hlo_analysis import HBM_BW, ICI_BW
    hbm_bw = hbm_bw or HBM_BW
    rules = serve_layout_rules(layout)
    stationary = serve_layout_rules("stationary")

    p_bytes = sharded_bytes(model.param_defs(), mesh, rules)
    in_bytes = sharded_bytes(model.input_defs(shape), mesh, rules)
    c_bytes, c_stream = _cache_bytes(model, shape, mesh, rules, cache_spec)
    if shape.kind == "prefill" and not cache_spec:
        # historical baseline: prefill peak modeled without the cache
        # output (kept so the default 3-layout table is stable); product
        # candidates carry a cache_spec and count it.
        c_bytes = c_stream = 0.0
    # activation workspace: ~2 live (tokens/dev, d_model) bf16 copies
    sizes = {str(k): int(v) for k, v in dict(mesh.shape).items()}
    data_deg = sizes.get("data", 1) * sizes.get("pod", 1)
    toks = shape.global_batch * (1 if shape.kind == "decode" else
                                 shape.seq_len)
    n_chunks = 1
    peak_toks = toks
    if chunked:
        n_chunks = max(1, math.ceil(shape.seq_len / CHUNK_TOKENS))
        peak_toks = shape.global_batch * min(CHUNK_TOKENS, shape.seq_len)
    act_peak = 2.0 * (peak_toks / max(data_deg, 1)) * \
        getattr(model.cfg, "d_model", 1) * 2
    act_total = 2.0 * (toks / max(data_deg, 1)) * \
        getattr(model.cfg, "d_model", 1) * 2

    # weight bytes that must be gathered per step to run stationary-style
    # compute (0 for stationary by construction); chunked prefill streams
    # (and re-gathers) the weights once per chunk.
    p_stationary = sharded_bytes(model.param_defs(), mesh, stationary)
    gather_bytes = max(p_stationary - p_bytes, 0.0)
    step = (p_bytes * n_chunks + c_stream + act_total) / hbm_bw \
        + gather_bytes * n_chunks / ICI_BW
    return CandidateEval(
        layout=layout,
        hbm_bytes=p_bytes + c_bytes + in_bytes + act_peak,
        step_time_s=step,
        source="analytic",
        detail={"param_bytes": p_bytes, "cache_bytes": c_bytes,
                "cache_stream_bytes": c_stream,
                "activation_bytes": act_peak,
                "gather_bytes_per_step": gather_bytes,
                "n_chunks": n_chunks},
        cache=cache_spec or "", chunked=chunked)


# ---------------------------------------------------------------------------
# Decision
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayoutDecision:
    """The chosen (layout, cache_spec, chunked) plus the full
    per-candidate scoring table.  `cache_spec`/`chunked` default to
    ""/False so pre-CacheSpec decisions (and tests constructing the
    dataclass positionally) keep working."""
    layout: str
    fits: bool                      # chosen candidate under budget*margin?
    budget_bytes: float
    margin: float
    evals: tuple                    # CandidateEval, in evaluation order
    reason: str
    cache_spec: str = ""            # "" = the model's config default
    chunked: bool = False

    @property
    def rules(self):
        return serve_layout_rules(self.layout)

    @property
    def key(self) -> str:
        k = self.layout
        if self.cache_spec:
            k += f"+{self.cache_spec}"
        if self.chunked:
            k += "+chunked"
        return k

    @property
    def chosen(self) -> CandidateEval:
        for e in self.evals:
            if e.key == self.key:
                return e
        for e in self.evals:           # pre-CacheSpec decision records
            if e.layout == self.layout:
                return e
        raise KeyError(self.key)

    def headroom_bytes(self, e: CandidateEval | None = None) -> float:
        e = e or self.chosen
        return self.budget_bytes * self.margin - e.hbm_bytes

    def as_dict(self) -> dict:
        return {
            "layout": self.layout, "fits": self.fits,
            **({"cache_spec": self.cache_spec} if self.cache_spec else {}),
            **({"chunked": True} if self.chunked else {}),
            "budget_gb": round(self.budget_bytes / 1e9, 2),
            "margin": self.margin,
            "headroom_gb": round(self.headroom_bytes() / 1e9, 3),
            "reason": self.reason,
            "candidates": [e.as_dict() for e in self.evals],
        }


def decide(evals, *, budget_bytes: float = DEVICE_HBM_BYTES,
           margin: float = DEFAULT_MARGIN) -> LayoutDecision:
    """Headroom-aware scoring: feasible = peak HBM <= budget*margin; the
    fastest feasible candidate wins (ties: first in `evals` order, which
    callers pass most-stationary-first, default-cache-first).  With no
    feasible candidate the smallest peak wins and `fits=False` (huge-MoE
    fallback)."""
    evals = tuple(evals)
    if not evals:
        raise ValueError("no candidate evaluations")
    cap = budget_bytes * margin
    feasible = [e for e in evals if e.hbm_bytes <= cap]
    if feasible:
        best = min(feasible, key=lambda e: e.step_time_s)
        reason = (f"{best.key}: peak {best.hbm_bytes/1e9:.2f} GB <= "
                  f"{cap/1e9:.2f} GB budget "
                  f"(headroom {(cap-best.hbm_bytes)/1e9:.2f} GB), fastest "
                  f"feasible step {best.step_time_s:.3g}s of "
                  f"{len(feasible)}/{len(evals)} feasible")
        return LayoutDecision(best.layout, True, budget_bytes, margin,
                              evals, reason, cache_spec=best.cache,
                              chunked=best.chunked)
    best = min(evals, key=lambda e: e.hbm_bytes)
    reason = (f"no layout fits under {cap/1e9:.2f} GB "
              f"({margin:.0%} of {budget_bytes/1e9:.0f} GB); falling back "
              f"to min-peak {best.key} at {best.hbm_bytes/1e9:.2f} GB "
              f"(over by {(best.hbm_bytes-cap)/1e9:.2f} GB)")
    return LayoutDecision(best.layout, False, budget_bytes, margin,
                          evals, reason, cache_spec=best.cache,
                          chunked=best.chunked)


def choose_serve_layout(evaluate, *, layouts=None,
                        budget_bytes: float = DEVICE_HBM_BYTES,
                        margin: float = DEFAULT_MARGIN) -> LayoutDecision:
    """Evaluate every candidate layout with `evaluate(name) ->
    CandidateEval` (most-stationary-first order) and decide."""
    layouts = list(layouts) if layouts is not None else list(SERVE_LAYOUTS)
    return decide([evaluate(name) for name in layouts],
                  budget_bytes=budget_bytes, margin=margin)


def serve_product_candidates(model, shape):
    """(layout, cache_spec, chunked) product candidates for one serve
    cell, in preference order: layouts most-stationary-first; within a
    layout the historical head/bf16 convention first, exotic specs after;
    chunked-prefill variants last (they pay n_chunks weight re-reads).

    Cache specs only enter the product for cells that HAVE a spec'able
    cache (decode/prefill on transformer families).  Chunked prefill is
    excluded for VLM-stub models (the patch_embeds prefix assumes
    one-shot prefill) and enc-dec archs (cross-attention frames)."""
    has_cache = (shape.kind in ("decode", "prefill")
                 and model._cache_defs is not None
                 and model.supports_cache_spec)
    chunk_ok = (shape.kind == "prefill" and has_cache
                and getattr(model.cfg, "frontend", "none") == "none"
                and not model.cfg.is_encdec
                and shape.seq_len > CHUNK_TOKENS)
    out = []
    for layout in SERVE_LAYOUTS:
        if not has_cache:
            out.append((layout, None, False))
            continue
        for spec in CACHE_SPEC_CANDIDATES:
            out.append((layout, spec, False))
    if chunk_ok:
        for layout in SERVE_LAYOUTS:
            for spec in CACHE_SPEC_CANDIDATES:
                out.append((layout, spec, True))
    return out


def analytic_serve_decision(model, shape, mesh, *,
                            budget_bytes: float = DEVICE_HBM_BYTES,
                            margin: float = DEFAULT_MARGIN) -> LayoutDecision:
    """Compile-free decision for serve launchers (serve.py / ServeLoop):
    scores the full (weight layout x cache spec [x chunked]) product."""
    evals = [analytic_eval(model, shape, mesh, layout, cache_spec=spec,
                           chunked=ch)
             for layout, spec, ch in serve_product_candidates(model, shape)]
    return decide(evals, budget_bytes=budget_bytes, margin=margin)
