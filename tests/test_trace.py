"""The program's host spans (repro/trace.py), the paged serve loop's
counters, and the named scopes of the decode step.

Spans are read back from a real profiler session on the CPU: the
`.xplane.pb` it writes holds every `flight.*` annotation with its start,
end and stats."""
import glob
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch.serve_loop import PagedServeLoop, Request
from repro.models import build_model
from repro.trace import PREFIX, span


def profiled(tmp_path, fn):
    """Run fn() under a profiler session; (fn's result, every host event
    named flight.* as (name, start_ns, end_ns, stats))."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    pb = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                   recursive=True)
    events = []
    for plane in ProfileData.from_file(pb[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(e.name[len(PREFIX):], e.start_ns, e.end_ns,
                            dict(e.stats)) for e in line.events
                           if e.name.startswith(PREFIX)]
    return out, events


def inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module")
def granite():
    cfg = get_smoke_config("granite-20b")
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.key(4))


def requests(cfg, lens, max_new, seed=4):
    rng = np.random.default_rng(seed)
    return [Request(rid=10 + i, prompt=rng.integers(0, cfg.vocab_size, n)
                    .astype(np.int32), max_new=max_new)
            for i, n in enumerate(lens)]


def test_span_is_a_named_annotation(tmp_path):
    def body():
        with span("outer", rid=3):
            with span("inner"):
                jnp.ones(4).block_until_ready()
    _, ev = profiled(tmp_path, body)
    got = {n: (s, e, st) for n, s, e, st in ev}
    assert set(got) == {"outer", "inner"}
    assert got["outer"][2] == {"rid": 3}
    assert inside(("inner", *got["inner"][:2]), ("outer", *got["outer"][:2]))


def test_paged_tick_spans_nest(tmp_path, granite):
    """Every phase span of a tick lies inside that tick; prefill inside its
    admission; admissions carry the request's rid, prefills the rid and
    the chunk count."""
    cfg, model, params = granite
    loop = PagedServeLoop(model, params, max_batch=2, num_blocks=32,
                          block_size=8, chunk=16)
    reqs = requests(cfg, (21, 40, 9), max_new=4)
    for r in reqs:
        loop.submit(r)
    done, ev = profiled(tmp_path, loop.run_until_drained)
    assert len(done) == 3
    by = lambda n: [e for e in ev if e[0] == n]
    ticks = by("serve.tick")
    assert len(ticks) >= loop.counters["decode_steps"]
    for name in ("serve.admit", "serve.grow", "serve.step",
                 "serve.readback"):
        assert by(name), name
        for e in by(name):
            assert any(inside(e, t) for t in ticks), (name, e)
    assert len(by("serve.step")) == loop.counters["decode_steps"]
    admits, prefills = by("serve.admit"), by("serve.prefill")
    assert sorted(a[3]["rid"] for a in admits) == [10, 11, 12]
    assert len(prefills) == 3
    for p in prefills:
        owner = [a for a in admits if inside(p, a)]
        assert [a[3]["rid"] for a in owner] == [p[3]["rid"]]
        T = len(reqs[p[3]["rid"] - 10].prompt)
        assert p[3]["chunks"] == math.ceil(T / 16)
    assert sum(p[3]["chunks"] for p in prefills) \
        == loop.counters["prefill_chunks"]


def drain_watching(loop, reqs):
    """Drain tick by tick, observing from outside what the counters
    count: live slots decoded per tick, the pool blocks those slots hold
    (a request that has emitted n tokens wrote position T + n - 2 in the
    step, so holds ceil((T + n - 1) / block_size) blocks), and
    preemptions (a preempted request's output list is replaced)."""
    for r in reqs:
        loop.submit(r)
    seen = {r.rid: r.out for r in reqs}
    live_per_step, blocks, preempted, done = [], 0, 0, []
    while loop.live or loop.queue:
        finished = loop.tick()
        done += finished
        decoded = list(loop.live.values()) + finished
        if decoded:
            live_per_step.append(len(decoded))
        blocks += sum(math.ceil((len(r.prompt) + len(r.out) - 1) / loop.bs)
                      for r in decoded)
        for r in reqs:
            if r.out is not seen[r.rid]:
                preempted += 1
                seen[r.rid] = r.out
    return done, live_per_step, blocks, preempted


def test_paged_counters_exact(granite):
    """The forced preemption of tests/test_serve_loop.py: 9 blocks x 8
    hold 72 positions for 3 x (>= 21 + 16) needed at once."""
    cfg, model, params = granite
    loop = PagedServeLoop(model, params, max_batch=3, num_blocks=9,
                          block_size=8, chunk=16)
    reqs = requests(cfg, (21, 23, 22), max_new=16)
    done, live_per_step, blocks, preempted = drain_watching(loop, reqs)
    c = loop.counters
    assert len(done) == 3 and preempted >= 1
    assert c["preemptions"] == preempted
    assert c["kv_blocks_read"] == blocks
    assert c["admissions"] == len(reqs) + preempted
    assert c["decode_steps"] == len(live_per_step)
    assert c["host_syncs"] == c["decode_steps"] + c["admissions"]
    # every admission prefilled its whole prompt (no shared prefix here)
    assert c["prefill_chunks"] >= sum(math.ceil(len(r.prompt) / 16)
                                      for r in reqs)


def test_paged_counters_without_preemption(granite):
    cfg, model, params = granite
    loop = PagedServeLoop(model, params, max_batch=2, num_blocks=64,
                          block_size=8, chunk=16)
    reqs = requests(cfg, (5, 17, 33), max_new=3, seed=5)
    done, live_per_step, blocks, preempted = drain_watching(loop, reqs)
    assert len(done) == 3 and preempted == 0
    # two decode steps per request, at positions T and T + 1 (blocks of 8)
    assert blocks == (1 + 1) + (3 + 3) + (5 + 5)
    assert loop.counters == {
        "decode_steps": len(live_per_step),
        "prefill_chunks": 1 + 2 + 3,
        "host_syncs": len(live_per_step) + 3,
        "admissions": 3,
        "preemptions": 0,
        "kv_blocks_read": blocks}


def test_fog_round_spans(tmp_path):
    """train_cohort's dispatch, the stacking of the base and the
    hierarchical exchange with its mixing and two hops, each a span; the
    hops inside the exchange."""
    from repro.core import federated, hierarchy
    from repro.core.client import LocalTrainer
    cfg = get_smoke_config("flight-cnn-mnist")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    W = 4
    rng = np.random.default_rng(0)
    images = rng.normal(size=(W, 8, 28, 28, 1)).astype(np.float32)
    labels = rng.integers(0, 10, (W, 8)).astype(np.int32)
    trainer = LocalTrainer(model, lr=0.05, batch_size=4)

    def round_():
        stacked = trainer.train_cohort(params, images, labels,
                                       jax.random.split(jax.random.key(1), W),
                                       epochs=1)
        return hierarchy.hierarchical_sync_aggregate(
            stacked, np.full(W, 8.0), np.arange(W) % 2, compress="q8",
            base_params=federated.stack_islands(params, W))

    out, ev = profiled(tmp_path, round_)
    jax.block_until_ready(out)
    names = [e[0] for e in ev]
    for n in ("fl.train", "fl.stack_islands", "fl.exchange", "fl.mixing",
              "fl.edge_hop", "fl.cloud_hop"):
        assert names.count(n) == 1, n
    ex = next(e for e in ev if e[0] == "fl.exchange")
    parts = [e for e in ev if e[0] in ("fl.mixing", "fl.edge_hop",
                                       "fl.cloud_hop")]
    assert all(inside(p, ex) for p in parts)
    assert [p[0] for p in sorted(parts, key=lambda p: p[1])] == [
        "fl.mixing", "fl.edge_hop", "fl.cloud_hop"]


def op_scopes(text: str) -> set[str]:
    """The named-scope paths found in a compiled program's op metadata."""
    parts = ("embed", "attention", "paged_gather", "mlp", "moe", "head")
    return {"/".join(p for p in m.split("/") if p in parts)
            for m in re.findall(r'op_name="([^"]*)"', text)} - {""}


def test_decode_step_named_scopes(granite):
    """The paged decode step's ops carry the part of the model they
    belong to; the gather sits inside attention."""
    cfg, model, params = granite
    loop = PagedServeLoop(model, params, max_batch=2, num_blocks=16,
                          block_size=8, chunk=16)
    args = (params, loop.pages, jnp.asarray(loop.bt),
            jnp.zeros((2, 1), jnp.int32), jnp.zeros((2, 1), jnp.int32))
    text = jax.jit(loop._decode_impl).lower(*args).compile().as_text()
    assert op_scopes(text) >= {"embed", "attention",
                               "attention/paged_gather", "mlp", "head"}


def test_moe_block_scope():
    cfg = get_smoke_config("mixtral-8x22b")
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    text = jax.jit(lambda p, t: model.apply(p, {"tokens": t})[0]) \
        .lower(params, tokens).as_text(debug_info=True)
    assert "moe/" in text and "attention/" in text
