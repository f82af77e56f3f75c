"""Layout-policy decision table + hbm_bytes calibration regressions.

Covers the three pieces the memory-aware serve layout rests on:
  * dist.policy.decide over tiny fake memory_analysis dicts (margin edge
    cases, tie-breaking, the huge-MoE nothing-fits fallback);
  * the HYBRID_SERVE_RULES factory (vocab tables shard over data, body
    weights stay stationary);
  * the calibrated fusion-boundary model: window reads for slice-only
    fusion params, and the end-to-end CNN-on-256-device cell landing
    within 2x of XLA's bytes-accessed.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.dist import hlo_cost, policy
from repro.dist.sharding import (HYBRID_SERVE_RULES, SERVE_RULES,
                                 abstract_mesh, logical_to_mesh_spec,
                                 serve_layout_rules)


def _eval(layout, args=0, temp=0, out=0, alias=0, bound_s=1.0):
    return policy.eval_from_compiled(
        layout,
        {"argument_size_in_bytes": args, "temp_size_in_bytes": temp,
         "output_size_in_bytes": out, "alias_size_in_bytes": alias},
        {"bound_s": bound_s})


GB = int(1e9)


# ---------------------------------------------------------------------------
# Decision table
# ---------------------------------------------------------------------------

def test_fastest_feasible_wins():
    d = policy.decide([
        _eval("stationary", args=10 * GB, bound_s=0.01),
        _eval("hybrid", args=6 * GB, bound_s=0.02),
        _eval("fsdp", args=2 * GB, bound_s=0.50),
    ], budget_bytes=16e9, margin=0.9)
    assert d.layout == "stationary" and d.fits
    assert d.headroom_bytes() == pytest.approx(16e9 * 0.9 - 10 * GB)
    assert "headroom" in d.reason


def test_over_budget_candidate_excluded():
    d = policy.decide([
        _eval("stationary", args=15 * GB, bound_s=0.01),   # > 14.4 GB cap
        _eval("fsdp", args=2 * GB, bound_s=0.50),
    ], budget_bytes=16e9, margin=0.9)
    assert d.layout == "fsdp" and d.fits


def test_margin_edge_exactly_at_cap_is_feasible():
    cap = 16e9 * 0.9
    d = policy.decide([_eval("stationary", args=int(cap), bound_s=0.01),
                       _eval("fsdp", args=GB, bound_s=1.0)],
                      budget_bytes=16e9, margin=0.9)
    assert d.layout == "stationary" and d.fits


def test_margin_edge_one_byte_over_cap_is_not():
    cap = 16e9 * 0.9
    d = policy.decide([_eval("stationary", args=int(cap) + 1, bound_s=0.01),
                       _eval("fsdp", args=GB, bound_s=1.0)],
                      budget_bytes=16e9, margin=0.9)
    assert d.layout == "fsdp"


def test_huge_moe_nothing_fits_falls_back_to_min_peak():
    d = policy.decide([
        _eval("stationary", args=55 * GB, bound_s=0.07),
        _eval("hybrid", args=27 * GB, bound_s=0.6),
        _eval("fsdp", args=20 * GB, bound_s=0.6),
    ], budget_bytes=16e9, margin=0.9)
    assert d.layout == "fsdp"
    assert not d.fits
    assert d.headroom_bytes() < 0
    assert "no layout fits" in d.reason


def test_step_time_tie_prefers_more_stationary():
    # evals arrive most-stationary-first; min() is stable on ties
    d = policy.decide([_eval("stationary", args=GB, bound_s=0.1),
                       _eval("hybrid", args=GB, bound_s=0.1),
                       _eval("fsdp", args=GB, bound_s=0.1)])
    assert d.layout == "stationary"


def test_peak_counts_nonaliased_output_only():
    # donated caches alias their argument: only out - alias adds to peak
    e = _eval("x", args=4 * GB, temp=GB, out=3 * GB, alias=3 * GB)
    assert e.hbm_bytes == pytest.approx(5 * GB)
    e2 = _eval("x", args=4 * GB, temp=GB, out=3 * GB, alias=0)
    assert e2.hbm_bytes == pytest.approx(8 * GB)


def test_decide_requires_candidates():
    with pytest.raises(ValueError):
        policy.decide([])


# ---------------------------------------------------------------------------
# Rule-set factory
# ---------------------------------------------------------------------------

def test_serve_layout_rules_factory():
    assert serve_layout_rules("stationary") is SERVE_RULES
    assert serve_layout_rules("hybrid") is HYBRID_SERVE_RULES
    with pytest.raises(KeyError):
        serve_layout_rules("nope")


def test_hybrid_shards_vocab_tables_over_model_and_data():
    mesh = abstract_mesh((4, 8), ("data", "model"))
    # the embedding table (vocab, embed): vocab takes the (model, data)
    # stack, the body d_model dim stays replicated
    spec = logical_to_mesh_spec(("vocab", "embed"), (64, 48), mesh,
                                HYBRID_SERVE_RULES)
    assert spec[0] == ("model", "data")
    assert spec[1] is None
    # body weights are untouched vs stationary serving
    for axes, shape in ((("embed", "ffn"), (48, 64)),
                        (("embed", "heads", None), (48, 8, 16))):
        assert logical_to_mesh_spec(axes, shape, mesh, HYBRID_SERVE_RULES) \
            == logical_to_mesh_spec(axes, shape, mesh, SERVE_RULES)


def test_hybrid_vocab_falls_back_to_model_when_indivisible():
    mesh = abstract_mesh((4, 8), ("data", "model"))
    # 24 divides by model=8 but not by model*data=32: longest divisible
    # prefix of the stack wins, same layout as stationary
    spec = logical_to_mesh_spec(("vocab", "embed"), (24, 48), mesh,
                                HYBRID_SERVE_RULES)
    assert spec[0] == "model"


# ---------------------------------------------------------------------------
# Calibrated fusion-boundary model
# ---------------------------------------------------------------------------

_WINDOW_HLO = """
HloModule m

%fused_dus (p0: f32[4096,512], p1: f32[4096], p2: s32[]) -> f32[4096,512] {
  %p0 = f32[4096,512]{1,0} parameter(0)
  %p1 = f32[4096]{0} parameter(1)
  %p2 = s32[] parameter(2)
  %c0 = s32[] constant(0)
  %ds = f32[1,512]{1,0} dynamic-slice(f32[4096,512]{1,0} %p0, s32[] %p2, s32[] %c0), dynamic_slice_sizes={1,512}
  %ds2 = f32[1]{0} dynamic-slice(f32[4096]{0} %p1, s32[] %p2), dynamic_slice_sizes={1}
  %b = f32[1,512]{1,0} broadcast(f32[1]{0} %ds2), dimensions={0}
  %a = f32[1,512]{1,0} add(f32[1,512]{1,0} %ds, f32[1,512]{1,0} %b)
  ROOT %dus = f32[4096,512]{1,0} dynamic-update-slice(f32[4096,512]{1,0} %p0, f32[1,512]{1,0} %a, s32[] %p2, s32[] %c0)
}

%body (param: (s32[], f32[4096,512], f32[4096])) -> (s32[], f32[4096,512], f32[4096]) {
  %param = (s32[], f32[4096,512]{1,0}, f32[4096]{0}) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[4096,512]{1,0}, f32[4096]{0}) %param), index=0
  %big = f32[4096,512]{1,0} get-tuple-element((s32[], f32[4096,512]{1,0}, f32[4096]{0}) %param), index=1
  %vec = f32[4096]{0} get-tuple-element((s32[], f32[4096,512]{1,0}, f32[4096]{0}) %param), index=2
  %one = s32[] constant(1)
  %next = s32[] add(s32[] %i, s32[] %one)
  %upd = f32[4096,512]{1,0} fusion(f32[4096,512]{1,0} %big, f32[4096]{0} %vec, s32[] %i), kind=kLoop, calls=%fused_dus
  ROOT %out = (s32[], f32[4096,512]{1,0}, f32[4096]{0}) tuple(s32[] %next, f32[4096,512]{1,0} %upd, f32[4096]{0} %vec)
}

%cond (param.1: (s32[], f32[4096,512], f32[4096])) -> pred[] {
  %param.1 = (s32[], f32[4096,512]{1,0}, f32[4096]{0}) parameter(0)
  %i.1 = s32[] get-tuple-element((s32[], f32[4096,512]{1,0}, f32[4096]{0}) %param.1), index=0
  %n = s32[] constant(4096)
  ROOT %lt = pred[] compare(s32[] %i.1, s32[] %n), direction=LT
}

ENTRY %main (arg: (s32[], f32[4096,512], f32[4096])) -> (s32[], f32[4096,512], f32[4096]) {
  %arg = (s32[], f32[4096,512]{1,0}, f32[4096]{0}) parameter(0)
  ROOT %w = (s32[], f32[4096,512]{1,0}, f32[4096]{0}) while((s32[], f32[4096,512]{1,0}, f32[4096]{0}) %arg), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"4096"}}
}
"""


def test_fusion_slice_only_params_charge_windows():
    """A 4096-trip loop whose fusion slices one row per trip must charge
    ~one full pass over the arrays, not 4096 full passes."""
    c = hlo_cost.analyze(_WINDOW_HLO)
    full_pass = 4096 * 512 * 4          # the big array, once
    # per trip: row read (2 KB) + scalar + row write -> ~2 passes total
    assert c["hbm_bytes"] < 4 * full_pass
    assert c["hbm_bytes"] > 0.5 * full_pass
    # the un-calibrated model charged the full operand every trip:
    assert c["hbm_bytes"] < (4096 * full_pass) / 100


def test_fusion_non_slice_use_still_charges_full_operand():
    text = """
HloModule m

%f (p0: f32[1024,1024], p1: f32[1024,1024]) -> f32[1024,1024] {
  %p0 = f32[1024,1024]{1,0} parameter(0)
  %p1 = f32[1024,1024]{1,0} parameter(1)
  ROOT %a = f32[1024,1024]{1,0} add(f32[1024,1024]{1,0} %p0, f32[1024,1024]{1,0} %p1)
}

ENTRY %main (x: f32[1024,1024], y: f32[1024,1024]) -> f32[1024,1024] {
  %x = f32[1024,1024]{1,0} parameter(0)
  %y = f32[1024,1024]{1,0} parameter(1)
  ROOT %fu = f32[1024,1024]{1,0} fusion(f32[1024,1024]{1,0} %x, f32[1024,1024]{1,0} %y), kind=kLoop, calls=%f
}
"""
    c = hlo_cost.analyze(text)
    buf = 1024 * 1024 * 4
    assert c["hbm_bytes"] == pytest.approx(3 * buf)  # 2 reads + 1 write


# ---------------------------------------------------------------------------
# End-to-end calibration regression (compiles the CNN cell on a fake
# 256-device mesh in a subprocess: dryrun must set XLA_FLAGS pre-import)
# ---------------------------------------------------------------------------

def test_cnn_hbm_calibrated_vs_xla(tmp_path):
    """CNN train on the 256-device mesh: replicated-compute cells used to
    report ~3600x XLA's bytes-accessed through the select-and-scatter
    while loop; calibrated model must stay within 2x."""
    env = dict(os.environ, REPRO_DRYRUN_DIR="dryrun_test",
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH",
                                                              ""))
    root = Path(__file__).resolve().parents[1]
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "flight-cnn-mnist", "--shape", "train_4k", "--mesh", "single",
         "--force"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    art = root / "artifacts" / "dryrun_test" / \
        "flight-cnn-mnist__train_4k__single.json"
    try:
        assert r.returncode == 0, r.stderr[-2000:]
        rec = json.loads(art.read_text())
        e = rec["entries"]["train_step"]
        ours = e["hlo_cost"]["hbm_bytes"]
        xla = e["xla_cost_analysis_once"]["bytes_accessed"]
        assert xla > 0
        assert ours <= 2.0 * xla, f"hbm_bytes {ours:.3g} vs XLA {xla:.3g}"
        assert ours >= 0.1 * xla, f"hbm_bytes {ours:.3g} vs XLA {xla:.3g}"
    finally:
        if art.exists():
            art.unlink()


class _Dev:
    def __init__(self, platform, stats):
        self.platform, self.device_kind, self._stats = platform, "x", stats

    def memory_stats(self):
        return self._stats


def test_device_hbm_budget_comes_from_the_attached_device():
    """CPU (AOT dry-runs, tests) keeps the modeled v5e constant; an
    attached accelerator is sized by its own bytes_limit, and one that
    reports none is an error rather than a silent 16 GB."""
    assert policy.device_hbm_bytes(jax.devices("cpu")[0]) == \
        policy.DEVICE_HBM_BYTES
    assert policy.device_hbm_bytes(_Dev("tpu", {"bytes_limit": 123})) == 123
    for stats in ({}, None):
        with pytest.raises(ValueError, match="bytes_limit"):
            policy.device_hbm_bytes(_Dev("tpu", stats))
