"""Bring-up check: the system's main paths on a TPU, at real width.

  python3 chip_smoke.py               # one chip
  python3 chip_smoke.py --four-chips  # four chips: the island exchange only

One chip runs four phases in this one process, through the entry points a
user calls:

  device     -- jax.devices()[0] must be a TPU; anything else stops the
                run before a model is built (exit 2, no result line).
  paged      -- qwen1.5-4b at its published widths (random weights from
                --seed) served by launch/serve.py's paged path
                (PagedServeLoop): 8 requests, prompts of 128-512 tokens,
                32 new tokens, max_batch 4, so requests join mid-flight.
                Tokens are checked against a teacher-forced forward pass.
  int8_cache -- the same weights through serve.py's contiguous path with
                the head/int8 KV cache; the decode step must hold the
                Pallas quant8 kernel and its logits must stay within the
                bound tests/test_cache_spec.py pins.
  federated  -- 64 flight-cnn-cifar workers trained as one vmapped cohort
                on non-IID syncifar shards, folded edge -> fog -> cloud by
                the int8 exchange (core/hierarchy.py); the Pallas exchange
                must match the jnp reference.

--four-chips runs only the island path of launch/train.py (4 islands, one
per chip, q8 exchange) and the same islands stacked on one device, and
compares the two.

Each phase prints one JSON line with its checks, compile and run seconds
and the device's peak memory.  Those times are bring-up diagnostics of a
cold process, not benchmark metrics.  The last line of stdout is
{"ok": <all phases passed>, "device": {"platform", "kind", "count"}}; the
exit code is 0 only when every phase passed.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402

#: max-abs bound of the Pallas exchange against the jnp reference (the
#: bound benchmarks/fl_exchange.py holds the kernel to).
EXCHANGE_PARITY = 1e-2
#: share of the decisive later tokens the paged path must agree on.
MIN_AGREEMENT = 0.9
#: stacked-vs-sharded islands: per-step loss and per-leaf scale-relative
#: param difference (a few bf16 ulps: the two programs tile differently).
FOUR_CHIP_LOSS_TOL = 1e-2
FOUR_CHIP_PARAM_TOL = 2e-2

class CompileClock:
    """Seconds the XLA backend spends compiling, from JAX's own monitoring
    events (tracing and lowering nest and are left out)."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs


def has_kernel(compiled) -> bool:
    """Does a compiled program hold a Pallas (Mosaic) kernel?"""
    return "tpu_custom_call" in compiled.as_text()


def decisive(ref, threshold):
    """Rows of `ref` logits whose top-2 gap exceeds `threshold`: there a
    perturbation that moves no logit by more than threshold / 2 cannot
    flip the argmax."""
    top2 = np.sort(ref, axis=-1)[..., -2:]
    return (top2[..., 1] - top2[..., 0]) > threshold


def model_steps(model):
    """Jitted logits-returning prefill / decode / full forward of a model."""
    prefill = jax.jit(lambda p, b: model.apply(p, b, mode="prefill"))
    decode = jax.jit(lambda p, b, c: model.apply(p, b, mode="decode",
                                                 cache=c))
    forward = jax.jit(lambda p, t, rows: jnp.take(
        model.apply(p, {"tokens": t}, mode="train")[0][0], rows,
        axis=0).astype(jnp.float32))
    return prefill, decode, forward


def forced_logits(steps, params, tokens, T: int, n: int,
                  edit=lambda cache: cache):
    """Teacher forcing through the serve cache: prefill tokens[:T], then
    n decode steps fed tokens[T + i] (the prefill cache passes through
    `edit` first).  Returns (n + 1, V) fp32 logits; row i predicts
    tokens[T + i]."""
    prefill, decode, _ = steps
    logits, cache = prefill(params, {"tokens": jnp.asarray(tokens[None, :T])})
    cache = edit(cache)
    out = [logits[0, -1]]
    for i in range(n):
        logits, cache = decode(params, {
            "tokens": jnp.asarray(tokens[None, T + i: T + i + 1]),
            "positions": jnp.full((1, 1), T + i, jnp.int32)}, cache)
        out.append(logits[0, -1])
    return np.asarray(jnp.stack(out), np.float32)


@jax.jit
def int4_kv(cache):
    """A bf16 cache with K and V rounded rowwise (over head_dim) to 15
    levels: an int4 cache, the control the int8 bound must reject."""
    def rq(x):
        x32 = x.astype(jnp.float32)
        s = jnp.max(jnp.abs(x32), axis=-1, keepdims=True) / 7
        return (jnp.round(x32 / jnp.maximum(s, 1e-12)) * s).astype(x.dtype)
    return dict(cache, k=rq(cache["k"]), v=rq(cache["v"]))


def full_forward(steps, params, tokens, rows, width: int):
    """Full-sequence logits at `rows` of `tokens` zero-padded to `width`
    (causal: padding never reaches earlier rows; one compile for all)."""
    padded = np.zeros((1, width), np.int32)
    padded[0, :len(tokens)] = tokens
    return np.asarray(steps[2](params, jnp.asarray(padded),
                               jnp.asarray(rows, jnp.int32)))


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_device(count: int):
    devs = jax.devices()
    d = devs[0]
    res = {"platform": d.platform, "kind": d.device_kind,
           "count": len(devs),
           "bytes_limit": (d.memory_stats() or {}).get("bytes_limit")}
    res["ok"] = d.platform == "tpu" and len(devs) >= count
    return res


def phase_paged(cfg, *, seed: int, n_requests: int = 8,
                prompt_lens=(128, 512), gen: int = 32, max_batch: int = 4,
                block_size: int = 16, probe_steps: int = 4):
    """Paged serving at `cfg`'s widths, checked by teacher forcing.

    The first generated token of every request must equal the forward's
    argmax.  For the later tokens the bf16 noise is measured first: one
    seeded sequence is teacher-forced through the serve cache (prefill +
    decode) and through the full forward; the largest |difference| of
    their logits is the noise.  A later token counts only where the
    forward's top-2 gap exceeds that noise (bf16 logits tie often at full
    vocabulary width), and the counted tokens must cover at least a
    quarter of the later ones.  Agreement over all tokens is reported
    too."""
    from repro.launch import serve
    from repro.launch.serve_loop import Request
    rng = np.random.default_rng(seed)
    lo, hi = prompt_lens
    T_max = hi + gen
    model, params, decision = serve.load(cfg, batch=max_batch,
                                         seq_len=T_max, seed=seed)
    steps = model_steps(model)
    width = -(-T_max // 64) * 64

    probe = rng.integers(0, cfg.vocab_size, lo + probe_steps).astype(np.int32)
    cached = forced_logits(steps, params, probe, lo, probe_steps)
    fwd = full_forward(steps, params, probe,
                       np.arange(lo - 1, lo + probe_steps), width)
    noise = float(np.abs(cached - fwd).max())

    requests = [Request(rid=i, prompt=rng.integers(
        0, cfg.vocab_size, int(rng.integers(lo, hi + 1))).astype(np.int32),
        max_new=gen) for i in range(n_requests)]
    nb = max_batch * -(-T_max // block_size) + 1
    done, loop, wall = serve.serve_paged(
        model, params, requests, max_batch=max_batch, block_size=block_size,
        num_blocks=nb, layout=decision.layout)

    first_ok = first_n = later_ok = later_n = first_all = later_all = 0
    for r in done:
        T = len(r.prompt)
        seq = np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
        ref = full_forward(steps, params, seq, np.arange(T - 1, T + gen - 1),
                           width)
        keep = decisive(ref, noise)
        agree = ref.argmax(-1) == np.asarray(r.out)
        first_n += int(keep[0])
        first_ok += int(keep[0] and agree[0])
        later_n += int(keep[1:].sum())
        later_ok += int((keep[1:] & agree[1:]).sum())
        first_all += int(agree[0])
        later_all += int(agree[1:].sum())
    rate = later_ok / max(later_n, 1)
    later_total = (gen - 1) * len(done)
    res = {"requests": len(done), "tokens": sum(len(r.out) for r in done),
           "prompt_lens": [len(r.prompt) for r in requests],
           "pool_blocks": nb, "preemptions": loop.counters["preemptions"],
           "bf16_noise": noise, "first_decisive": first_n,
           "first_agree": first_ok, "later_decisive": later_n,
           "later_agree": later_ok, "agreement_rate": rate,
           "first_agree_all": first_all, "later_agree_all": later_all,
           "later_total": later_total, "serve_wall_s": wall}
    res["ok"] = (len(done) == n_requests
                 and all(len(r.out) == gen for r in done)
                 and first_all == len(done)
                 and 4 * later_n >= later_total
                 and rate >= MIN_AGREEMENT)
    return res, params


def phase_int8(cfg, params, *, seed: int, on_chip: bool, T: int = 128,
               n: int = 4):
    """The contiguous serve path with the head/int8 KV cache.  Its
    teacher-forced logits must stay within INT8_LOGIT_BOUND of the bf16
    cache's, and the bf16 cache rounded to int4 levels must not."""
    from repro.launch import serve
    from repro.launch.steps import make_decode_step
    from repro.models.cache import INT8_LOGIT_BOUND
    rng = np.random.default_rng(seed + 1)
    model8, params, decision = serve.load(cfg, batch=1, seq_len=T + n,
                                          cache="head/int8", params=params)
    model16, _, _ = serve.load(cfg, batch=1, seq_len=T + n,
                               cache="head/bf16", params=params)
    tokens = rng.integers(0, cfg.vocab_size, T + n).astype(np.int32)
    batch = {"tokens": jnp.asarray(tokens[None, :T])}
    out, _, _ = serve.serve_contiguous(model8, params, batch, gen=n,
                                       rules=decision.rules)
    cache = jax.eval_shape(
        lambda p, b: model8.apply(p, b, mode="prefill")[1], params, batch)
    step = jax.jit(make_decode_step(model8)).lower(
        params, {"tokens": jnp.zeros((1, 1), jnp.int32),
                 "positions": jnp.full((1, 1), T, jnp.int32)},
        cache).compile()
    steps16 = model_steps(model16)
    ref = forced_logits(steps16, params, tokens, T, n)
    got = forced_logits(model_steps(model8), params, tokens, T, n)
    int4 = forced_logits(steps16, params, tokens, T, n, edit=int4_kv)
    d = np.abs(got - ref)
    keep = decisive(ref, 2 * d.max(-1))
    rel_int4 = float(np.abs(int4 - ref).max() / np.abs(ref).max())
    res = {"cache_spec": decision.cache_spec, "generated": out.shape[1],
           "decode_has_kernel": has_kernel(step),
           "rel_max_err": float(d.max() / np.abs(ref).max()),
           "rel_bound": INT8_LOGIT_BOUND,
           "rms_err": float(np.sqrt((d ** 2).mean())),
           "argmax_decisive": int(keep.sum()),
           "argmax_agree": int((got.argmax(-1) == ref.argmax(-1))[keep].sum()),
           "int4_control": {"rel_max_err": rel_int4,
                            "ok": rel_int4 <= INT8_LOGIT_BOUND}}
    res["ok"] = (res["cache_spec"] == "head/int8"
                 and (res["decode_has_kernel"] or not on_chip)
                 and res["rel_max_err"] <= res["rel_bound"]
                 and not res["int4_control"]["ok"]
                 and res["argmax_agree"] == res["argmax_decisive"])
    return res


def noniid_shards(n_workers: int, per_worker: int, *, seed: int,
                  alpha: float = 0.5):
    """(images (W, S, 32, 32, 3), labels (W, S)): each worker's labels
    follow its own Dirichlet(alpha) draw over the 10 classes."""
    from repro.data.synthetic import make_classification_set
    x, y = make_classification_set("syncifar", 2 * n_workers * per_worker,
                                   seed=seed)
    rng = np.random.default_rng(seed)
    by_class = [np.flatnonzero(y == c) for c in range(10)]
    idx = []
    for _ in range(n_workers):
        counts = rng.multinomial(per_worker, rng.dirichlet([alpha] * 10))
        idx.append(rng.permutation(np.concatenate(
            [rng.choice(by_class[c], k) for c, k in enumerate(counts)])))
    idx = np.stack(idx)
    return x[idx], y[idx]


def phase_federated(*, seed: int, on_chip: bool, n_workers: int = 64,
                    per_worker: int = 128, fog_cells: int = 4,
                    rounds: int = 3, batch_size: int = 32, lr: float = 0.02):
    """A cohort round of the paper's CIFAR CNN through the fog tier."""
    from repro.configs import get_config
    from repro.core import federated, hierarchy
    from repro.core.client import LocalTrainer, softmax_xent
    from repro.models import build_model
    model = build_model(get_config("flight-cnn-cifar"))
    trainer = LocalTrainer(model, lr=lr, batch_size=batch_size)
    images, labels = noniid_shards(n_workers, per_worker, seed=seed)
    images, labels = jnp.asarray(images), jnp.asarray(labels)
    weights = np.full(n_workers, float(per_worker))
    cell_of = np.arange(n_workers) % fog_cells

    def loss_of(p, x, y):
        return softmax_xent(model.apply(p, {"images": x})[0], y)

    member_loss = jax.jit(jax.vmap(loss_of))           # stacked params
    global_loss = jax.jit(jax.vmap(loss_of, in_axes=(None, 0, 0)))

    def exchange(impl):
        return jax.jit(lambda s, b: hierarchy.hierarchical_sync_aggregate(
            s, weights, cell_of, compress="q8", base_params=b, impl=impl))

    pallas, ref = exchange("pallas"), exchange("ref")
    params = model.init(jax.random.key(seed))
    keys = jax.random.split(jax.random.key(seed + 1), rounds * n_workers)
    start = float(global_loss(params, images, labels).mean())
    res = {"workers": n_workers, "fog_cells": fog_cells, "rounds": rounds,
           "loss_before": [], "loss_after": [], "exchange_max_abs": []}
    for r in range(rounds):
        before = float(global_loss(params, images, labels).mean())
        stacked = trainer.train_cohort(
            params, images, labels, keys[r * n_workers:(r + 1) * n_workers],
            epochs=1)
        after = float(member_loss(stacked, images, labels).mean())
        base = federated.stack_islands(params, n_workers)
        got, want = pallas(stacked, base), ref(stacked, base)
        res["loss_before"].append(before)
        res["loss_after"].append(after)
        res["exchange_max_abs"].append(max(
            float(jnp.abs(a - b).max())
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))))
        params = federated.island_slice(got, 0)
    res["exchange_has_kernel"] = has_kernel(
        pallas.lower(stacked, base).compile())
    res["loss_end"] = float(global_loss(params, images, labels).mean())
    res["loss_start"] = start
    finite = np.isfinite(res["loss_before"] + res["loss_after"]).all()
    res["ok"] = bool(
        finite
        and all(a < b for a, b in zip(res["loss_after"], res["loss_before"]))
        and res["loss_end"] < start
        and max(res["exchange_max_abs"]) <= EXCHANGE_PARITY
        and (res["exchange_has_kernel"] or not on_chip))
    return res


def phase_four_chips(*, seed: int, on_chip: bool, cfg=None,
                     n_islands: int = 4, batch: int = 8, seq: int = 256):
    """launch/train.py's island path with one island per device (2 rounds
    of 2 local steps, q8 exchange), against the same islands stacked on
    one device.  `cfg` defaults to the ~100M granite-family decoder.

    The island path's q8 exchange is also held to the jnp reference on
    the run's own leaves: islands that moved from the initial params by
    1/4 .. 4/4 of the run's update, mixed by a seeded row-stochastic
    matrix, with the pod-sharded Pallas exchange against impl="ref"."""
    from repro.configs.granite_20b import HUNDRED_M
    from repro.core import federated
    from repro.launch import train
    from repro.launch.steps import make_fl_aggregate
    from repro.models import build_model
    cfg = cfg or HUNDRED_M
    args = train.parse_args([
        "--islands", str(n_islands), "--local-steps", "2", "--steps", "4",
        "--compress", "q8", "--batch", str(batch), "--seq", str(seq),
        "--seed", str(seed)])
    devices = jax.devices()[:n_islands]
    sharded = train.run(args, cfg=cfg, devices=devices)
    stacked = train.run(args, cfg=cfg, devices=devices[:1])

    mesh = train.island_mesh(n_islands, devices)
    leaf = jax.tree.leaves(sharded["params"])[0]
    spread = jnp.arange(1, n_islands + 1, dtype=jnp.float32) / n_islands
    mixing = jnp.asarray(np.random.default_rng(seed).dirichlet(
        np.ones(n_islands), n_islands), jnp.float32)
    with jax.set_mesh(mesh):
        base = jax.device_put(
            federated.stack_islands(build_model(cfg).init(
                jax.random.key(seed)), n_islands), leaf.sharding)
        moved = jax.tree.map(
            lambda p, b: (b + (p - b) * spread.reshape(
                (-1,) + (1,) * (p.ndim - 1))).astype(p.dtype),
            sharded["params"], base)
        pallas = jax.jit(make_fl_aggregate("q8"))
        exch = pallas.lower(moved, base, mixing).compile()
        got = pallas(moved, base, mixing)
        want = jax.jit(functools.partial(
            federated.fl_aggregate_compressed, mode="q8", impl="ref"))(
                moved, base, mixing)
        exchange_diff = max(
            float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    loss_diff = float(np.abs(np.subtract(sharded["losses"],
                                         stacked["losses"])).max())
    param_diff = 0.0
    for a, b in zip(jax.tree.leaves(sharded["params"]),
                    jax.tree.leaves(stacked["params"])):
        a = np.asarray(jax.device_get(a)).astype(np.float32)
        b = np.asarray(jax.device_get(b)).astype(np.float32)
        param_diff = max(param_diff, float(np.abs(a - b).max()
                                           / max(np.abs(b).max(), 1e-12)))
    res = {"islands": n_islands, "mesh": dict(mesh.shape),
           "island_axis_spec": str(leaf.sharding.spec),
           "island_devices": len(leaf.sharding.device_set),
           "losses_sharded": sharded["losses"],
           "losses_stacked": stacked["losses"],
           "loss_max_diff": loss_diff, "param_rel_max_diff": param_diff,
           "exchange_has_kernel": has_kernel(exch),
           "exchange_max_abs": exchange_diff}
    res["ok"] = (res["island_devices"] == n_islands
                 and leaf.sharding.spec[0] == "pod"
                 and np.isfinite(sharded["losses"]).all()
                 and loss_diff <= FOUR_CHIP_LOSS_TOL
                 and param_diff <= FOUR_CHIP_PARAM_TOL
                 and exchange_diff <= EXCHANGE_PARITY
                 and (res["exchange_has_kernel"] or not on_chip))
    return res


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def _run_phase(name, fn, clock, **kw):
    """Run one phase; print its JSON line; return (ok, value)."""
    c0, t0 = clock.seconds, time.time()
    value = None
    try:
        out = fn(**kw)
        res, value = out if isinstance(out, tuple) else (out, None)
    except Exception:                       # a failed phase must not stop
        traceback.print_exc()               # the others from reporting
        res = {"ok": False, "error": traceback.format_exc(limit=1)}
    wall = time.time() - t0
    compile_s = clock.seconds - c0
    stats = jax.devices()[0].memory_stats() or {}
    line = {"phase": name, **res, "compile_s": compile_s,
            "run_s": wall - compile_s,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}
    print(json.dumps(line, default=float), flush=True)
    return bool(res["ok"]), value


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip island exchange and its "
                         "single-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"'{dev.platform}' ({dev.device_kind}); nothing was run",
              file=sys.stderr)
        return 2
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    warm = os.path.isdir(cache_dir) and bool(os.listdir(cache_dir))
    print(json.dumps({"compile_cache": cache_dir, "cache_warm": warm}),
          flush=True)
    clock = CompileClock()
    count = 4 if args.four_chips else 1
    ok, _ = _run_phase("device", phase_device, clock, count=count)
    if ok and args.four_chips:
        ok, _ = _run_phase("four_chips", phase_four_chips, clock,
                           seed=args.seed, on_chip=True)
    elif ok:
        cfg = get_config("qwen1.5-4b")
        ok_paged, params = _run_phase("paged", phase_paged, clock, cfg=cfg,
                                      seed=args.seed)
        ok_int8 = params is not None and _run_phase(
            "int8_cache", phase_int8, clock, cfg=cfg, params=params,
            seed=args.seed, on_chip=True)[0]
        del params
        ok_fl, _ = _run_phase("federated", phase_federated, clock,
                              seed=args.seed, on_chip=True)
        ok = ok_paged and ok_int8 and ok_fl
    print(json.dumps({"ok": bool(ok), "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
