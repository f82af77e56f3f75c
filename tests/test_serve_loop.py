"""Continuous batching: ragged slots must reproduce solo-serving outputs,
and the block-table paged cache must reproduce the contiguous cache
token-for-token (incl. mid-flight joins, slot reuse, prefix sharing and
preemption)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch.serve_loop import PagedServeLoop, Request, ServeLoop
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models import build_model


def solo_generate(model, params, prompt, max_new):
    """Reference: serve one request alone through prefill+decode."""
    prefill = jax.jit(make_prefill_step(model))
    decode = jax.jit(make_decode_step(model))
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
    nxt, cache = prefill(params, {"tokens": toks})
    out = [int(nxt[0])]
    pos = len(prompt)
    while len(out) < max_new:
        nxt, cache = decode(params, {
            "tokens": nxt[:, None].astype(jnp.int32),
            "positions": jnp.full((1, 1), pos, jnp.int32)}, cache)
        out.append(int(nxt[0]))
        pos += 1
    return out


@pytest.mark.parametrize("arch", ["granite-20b", "falcon-mamba-7b"])
def test_continuous_batching_matches_solo(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (12, 7, 19)]
    want = [solo_generate(model, params, p, 6) for p in prompts]

    loop = ServeLoop(model, params, max_batch=2, max_len=128)
    reqs = [Request(rid=i, prompt=p, max_new=6)
            for i, p in enumerate(prompts)]
    for r in reqs:
        loop.submit(r)  # 3 requests > 2 slots: the third joins mid-flight
    done = loop.run_until_drained()
    assert len(done) == 3
    got = {r.rid: r.out for r in done}
    for i in range(3):
        assert got[i] == want[i], (i, got[i], want[i])


def test_slots_recycled_and_queue_drains():
    cfg = get_smoke_config("granite-20b")
    model = build_model(cfg)
    params = model.init(jax.random.key(1))
    loop = ServeLoop(model, params, max_batch=2, max_len=64)
    rng = np.random.default_rng(1)
    for i in range(5):
        loop.submit(Request(rid=i, prompt=rng.integers(
            0, cfg.vocab_size, 8).astype(np.int32), max_new=3))
    done = loop.run_until_drained()
    assert sorted(r.rid for r in done) == [0, 1, 2, 3, 4]
    assert all(len(r.out) == 3 for r in done)
    assert sorted(loop.free) == [0, 1]


# -- block-table paged cache ------------------------------------------------

def _drain(loop, prompts, max_new=6):
    for i, p in enumerate(prompts):
        loop.submit(Request(rid=i, prompt=p, max_new=max_new))
    done = loop.run_until_drained()
    assert len(done) == len(prompts)
    return {r.rid: r.out for r in done}


def test_paged_matches_contiguous_mid_flight_joins():
    """5 requests through 2 slots: the paged path (chunked+bucketed
    prefill, paged decode, slot reuse after eviction) must emit exactly
    the contiguous path's greedy token streams."""
    cfg = get_smoke_config("granite-20b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (12, 7, 19, 33, 5)]
    want = _drain(ServeLoop(model, params, max_batch=2, max_len=128),
                  prompts)
    ploop = PagedServeLoop(model, params, max_batch=2, num_blocks=32,
                           block_size=8, chunk=16)
    got = _drain(ploop, prompts)
    assert got == want
    ploop.alloc.check_invariants()
    assert not ploop.alloc.tables          # everything released
    assert ploop.alloc.n_free() == 32


def test_paged_prefix_sharing_is_token_identical():
    """Two prompts with a long common prefix: the second must re-use the
    first's full prefix blocks (no recompute) and still match the
    contiguous outputs exactly."""
    cfg = get_smoke_config("granite-20b")
    model = build_model(cfg)
    params = model.init(jax.random.key(3))
    rng = np.random.default_rng(3)
    base = rng.integers(0, cfg.vocab_size, 24).astype(np.int32)
    prompts = [np.concatenate([base, rng.integers(0, cfg.vocab_size, k)
                               .astype(np.int32)]) for k in (5, 3, 9)]
    want = _drain(ServeLoop(model, params, max_batch=3, max_len=128),
                  prompts, max_new=5)
    ploop = PagedServeLoop(model, params, max_batch=3, num_blocks=32,
                           block_size=8, chunk=16)
    got = _drain(ploop, prompts, max_new=5)
    assert got == want
    # 24-token prefix = 3 full blocks, shared by requests 1 and 2
    assert ploop.alloc.stats["shared_blocks"] >= 6


def test_paged_preemption_requeues_exactly():
    """A pool too small for all admitted sequences forces preemption; the
    requeued request must still produce the exact greedy stream."""
    cfg = get_smoke_config("granite-20b")
    model = build_model(cfg)
    params = model.init(jax.random.key(4))
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (21, 23, 22)]
    want = _drain(ServeLoop(model, params, max_batch=3, max_len=128),
                  prompts, max_new=16)
    # 9 blocks x 8 = 72 positions for 3 x (>=21+16) = 111+ needed at once
    ploop = PagedServeLoop(model, params, max_batch=3, num_blocks=9,
                           block_size=8, chunk=16)
    got = _drain(ploop, prompts, max_new=16)
    assert got == want
    assert ploop.counters["preemptions"] >= 1
    ploop.alloc.check_invariants()


def test_paged_rejects_stateful_families():
    cfg = get_smoke_config("falcon-mamba-7b")
    model = build_model(cfg)
    with pytest.raises(ValueError, match="paged"):
        PagedServeLoop(model, model.init(jax.random.key(0)))


# -- host/device length bookkeeping ----------------------------------------

def test_lengths_dtype_matches_device_positions():
    """Regression: ServeLoop.lengths was np.int64 while `_next`/positions
    are int32 -- the implicit cast silently wraps past 2^31.  Both loops
    must keep host lengths in int32, and values near the boundary must
    round-trip exactly into the positions array fed to decode."""
    cfg = get_smoke_config("granite-20b")
    model = build_model(cfg)
    params = model.init(jax.random.key(5))
    loop = ServeLoop(model, params, max_batch=2, max_len=32)
    ploop = PagedServeLoop(model, params, max_batch=2, num_blocks=8,
                           block_size=8)
    for lo in (loop, ploop):
        assert lo.lengths.dtype == np.int32
        assert lo._next.dtype == jnp.int32
    big = 2**31 - 2              # one decode step of headroom left
    loop.lengths[0] = big
    positions = jnp.asarray(loop.lengths.reshape(loop.B, 1), jnp.int32)
    assert positions.dtype == jnp.int32
    assert int(positions[0, 0]) == big, "host->device length must be exact"
    # the int64 host array used to make this silently disagree:
    skewed = np.zeros(2, np.int64)
    skewed[0] = 2**31 + 5        # would wrap negative through int32
    assert int(skewed.astype(np.int32)[0]) != skewed[0]


# -- spec'd caches through the continuous loop -----------------------------

def test_serve_loop_ring_cache_token_identical():
    """ServeLoop(cache_spec="ring:4/bf16") rebuilds the model around the
    spec'd cache (params untouched) and reproduces the baseline stream
    token-for-token -- the CacheSpec contract holding through slot reuse
    and mid-flight joins, not just single-request decode."""
    cfg = get_smoke_config("granite-20b")
    model = build_model(cfg)
    params = model.init(jax.random.key(9))
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (12, 7, 19)]

    def drain(spec):
        loop = ServeLoop(model, params, max_batch=2, max_len=128,
                         cache_spec=spec)
        if spec:
            assert loop.model.cfg.cache_spec == spec
        for i, p in enumerate(prompts):
            loop.submit(Request(rid=i, prompt=p, max_new=6))
        return {r.rid: r.out for r in loop.run_until_drained()}

    assert drain("ring:4/bf16") == drain(None)
