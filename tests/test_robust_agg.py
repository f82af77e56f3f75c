"""Property tests for the Byzantine-robust aggregators (core/aggregation.py).

  * permutation invariance: member order never changes the fold;
  * degenerate agreement: identical updates come back unchanged, and
    trim_frac=0 trimmed mean == the uniform mean;
  * breakdown bound: with f corrupt members at arbitrary magnitude and a
    matched trim/krum budget, the fold stays inside the honest members'
    coordinate-wise envelope (trimmed/median/krum) or norm ball (norm_clip).
Driven by hypothesis when installed, else the deterministic fallback shim.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # deterministic fallback (tests/_hypothesis_compat.py)
    from _hypothesis_compat import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core import aggregation as agg

members_st = st.integers(min_value=3, max_value=9)
seed_st = st.integers(min_value=0, max_value=2**31 - 1)


def make_members(P: int, seed: int):
    rng = np.random.default_rng(seed)
    return [{"w": jnp.asarray(rng.normal(size=(3, 2)), jnp.float32),
             "b": jnp.asarray(rng.normal(size=(4,)), jnp.float32)}
            for _ in range(P)]


def flat(tree) -> np.ndarray:
    return np.concatenate([np.asarray(l, np.float32).ravel()
                           for l in jax.tree.leaves(tree)])


BASE = {"w": jnp.zeros((3, 2), jnp.float32), "b": jnp.zeros(4, jnp.float32)}


@settings(max_examples=25, deadline=None)
@given(members_st, seed_st, st.sampled_from(agg.ROBUST_METHODS))
def test_permutation_invariance(P, seed, method):
    ms = make_members(P, seed)
    perm = np.random.default_rng(seed + 1).permutation(P)
    a = agg.robust_aggregate(ms, method, base=BASE)
    b = agg.robust_aggregate([ms[i] for i in perm], method, base=BASE)
    np.testing.assert_allclose(flat(a), flat(b), rtol=1e-5, atol=1e-6)


@settings(max_examples=25, deadline=None)
@given(members_st, seed_st, st.sampled_from(agg.ROBUST_METHODS))
def test_identical_updates_pass_through(P, seed, method):
    one = make_members(1, seed)[0]
    out = agg.robust_aggregate([one] * P, method, base=BASE)
    np.testing.assert_allclose(flat(out), flat(one), rtol=1e-5, atol=1e-6)


@settings(max_examples=25, deadline=None)
@given(members_st, seed_st)
def test_trim_zero_equals_uniform_mean(P, seed):
    ms = make_members(P, seed)
    out = agg.robust_aggregate(ms, "trimmed_mean", trim_frac=0.0)
    mean = np.mean([flat(m) for m in ms], axis=0)
    np.testing.assert_allclose(flat(out), mean, rtol=1e-5, atol=1e-6)


@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=5, max_value=11), seed_st,
       st.sampled_from(("trimmed_mean", "median", "krum")))
def test_breakdown_bound_with_f_corrupt(P, seed, method):
    """f = floor((P-1)/4) corrupt members at huge magnitude cannot pull the
    fold outside the honest coordinate-wise envelope (trimmed/median) or
    honest selection (krum with a matched f budget)."""
    f = max(1, (P - 1) // 4)
    ms = make_members(P, seed)
    big = 1e6
    for i in range(f):
        ms[i] = jax.tree.map(lambda l: l * 0 + big, ms[i])
    honest = np.stack([flat(m) for m in ms[f:]])
    out = flat(agg.robust_aggregate(
        ms, method, trim_frac=f / P, krum_f=f))
    lo, hi = honest.min(axis=0), honest.max(axis=0)
    assert np.all(out >= lo - 1e-4) and np.all(out <= hi + 1e-4)


@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=3, max_value=9), seed_st,
       st.floats(min_value=1.0, max_value=4.0))
def test_norm_clip_bounds_the_fold(P, seed, clip_mult):
    """After clipping, every member's delta norm is <= clip_mult x the
    median norm, so the weighted mean's delta norm is too."""
    ms = make_members(P, seed)
    ms[0] = jax.tree.map(lambda l: l * 1e5, ms[0])  # one runaway update
    norms = [agg.delta_norm(m, BASE) for m in ms]
    thr = clip_mult * float(np.median(norms))
    out = agg.robust_aggregate(ms, "norm_clip", base=BASE,
                               clip_mult=clip_mult)
    assert agg.delta_norm(out, BASE) <= thr + 1e-3


def test_trim_k_clamps():
    assert agg.trim_k(5, 0.2) == 1
    assert agg.trim_k(5, 0.5) == 2      # clamped: >= 1 survivor
    assert agg.trim_k(3, 0.9) == 1
    assert agg.trim_k(10, 0.0) == 0


def test_krum_excludes_far_outliers():
    rng = np.random.default_rng(0)
    ms = [{"w": jnp.asarray(rng.normal(size=(4,)), jnp.float32)}
          for _ in range(6)]
    ms.append({"w": jnp.full((4,), 1e6, jnp.float32)})
    sel = agg.krum_select(agg._stack_trees(ms), f=1)
    assert 6 not in sel                 # the outlier is never selected


def test_unknown_method_raises():
    with pytest.raises(ValueError):
        agg.robust_aggregate(make_members(3, 0), "no_such_method")


# ---- robust fold x compressed exchange -----------------------------------
# launch/train.py --robust-agg X --compress Y: the fold must see (and the
# quarantine gate must threshold) the DECOMPRESSED per-island deltas the
# wire actually carries, not full-precision local weights.

from repro.core import compression as comp
from repro.core.faults import finite_members


def _stacked(P, seed, scale=1.0):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(rng.normal(size=(P, 6, 4)) * scale, jnp.float32),
            "b": jnp.asarray(rng.normal(size=(P, 8)) * scale, jnp.float32)}


@pytest.mark.parametrize("mode", ["q8", "topk", "q8_topk"])
def test_roundtrip_islands_keeps_honest_members_finite(mode):
    P = 3
    stacked, base = _stacked(P, 0), _stacked(P, 1, scale=0.0)
    out = comp.roundtrip_islands(stacked, base, mode=mode, k_frac=0.2)
    assert finite_members(out).all()
    assert jax.tree.structure(out) == jax.tree.structure(stacked)
    assert all(a.shape == b.shape for a, b in
               zip(jax.tree.leaves(out), jax.tree.leaves(stacked)))


def test_roundtrip_islands_q8_reconstruction_bounded():
    """Per-island q8 wire: reconstruction error <= one quant step
    (amax / 127) of that island's OWN delta -- islands never share block
    scales."""
    P = 4
    stacked, base = _stacked(P, 2), _stacked(P, 3)
    out = comp.roundtrip_islands(stacked, base, mode="q8")
    for i in range(P):
        for k in ("w", "b"):
            delta = np.asarray(stacked[k][i] - base[k][i])
            err = np.abs(np.asarray(out[k][i]) - np.asarray(stacked[k][i]))
            assert err.max() <= np.abs(delta).max() / 127.0 + 1e-6


def test_roundtrip_islands_payloads_are_independent():
    """Corrupting island 1 must not move island 0's reconstruction by one
    bit: payloads (top-k selection, block scales) never straddle
    islands."""
    P = 2
    stacked, base = _stacked(P, 4), _stacked(P, 5, scale=0.0)
    ref = comp.roundtrip_islands(stacked, base, mode="q8_topk", k_frac=0.3)
    hot = jax.tree.map(lambda l: l.at[1].mul(1e6), stacked)
    got = comp.roundtrip_islands(hot, base, mode="q8_topk", k_frac=0.3)
    np.testing.assert_array_equal(np.asarray(ref["w"][0]),
                                  np.asarray(got["w"][0]))
    np.testing.assert_array_equal(np.asarray(ref["b"][0]),
                                  np.asarray(got["b"][0]))


@pytest.mark.parametrize("mode", ["topk", "q8_topk"])
def test_quarantine_gate_thresholds_decompressed_deltas(mode):
    """An inf smuggled into one island's delta has the largest magnitude,
    so top-k KEEPS it: the post-roundtrip finite_members gate (what
    train.py re-ands into `ok`) flags exactly that island while honest
    islands -- including one with a huge-but-finite delta -- pass."""
    P = 3
    stacked, base = _stacked(P, 6), _stacked(P, 7, scale=0.0)
    stacked = jax.tree.map(lambda l: l, stacked)
    stacked["w"] = stacked["w"].at[1, 0, 0].set(jnp.inf)   # corrupt island 1
    stacked["b"] = stacked["b"].at[2].mul(1e4)             # big-but-honest
    out = comp.roundtrip_islands(stacked, base, mode=mode, k_frac=0.2)
    ok = finite_members(out)
    assert not ok[1]
    assert ok[0] and ok[2]


def test_robust_agg_with_compression_converges(capsys):
    """End-to-end smoke: --compress q8-topk --robust-agg trimmed_mean
    trains through real exchanges and the loss goes down (the tier-1
    convergence gate for the robust x compressed composition)."""
    from repro.launch import train
    # run(), not main(): main() also turns on the persistent compilation
    # cache, which would then stay on for every later test in this worker
    train.run(train.parse_args([
        "--arch", "granite-20b", "--smoke", "--steps", "12",
        "--islands", "2", "--local-steps", "2", "--batch", "4",
        "--seq", "32", "--compress", "q8-topk",
        "--robust-agg", "trimmed_mean", "--seed", "0"]))
    lines = capsys.readouterr().out.splitlines()
    losses = [float(ln.split("loss=")[1].split()[0])
              for ln in lines if "loss=" in ln]
    assert len(losses) == 12
    # the exchange path actually ran (tagged robust+compressed)
    assert any("robust-exchange:trimmed_mean+q8-topk" in ln for ln in lines)
    assert losses[-1] < losses[0], losses
