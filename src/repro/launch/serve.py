"""Batched serving launcher: prefill a batch of prompts, decode greedily.

  PYTHONPATH=src python -m repro.launch.serve --arch falcon-mamba-7b \
      --smoke --batch 4 --prompt-len 64 --gen 32

Weight layout (stationary / hybrid / fsdp) is chosen by the memory-aware
policy in repro.dist.policy (`--layout auto`, the default), or forced
with `--layout <name>`, against the HBM the attached device reports
(`policy.device_hbm_bytes`).  The chosen RuleSet is ambient while the
steps trace, so `constrain()` calls in model code resolve against it; on
one device every layout degenerates to replicated and the decision is
only reported.

`load`, `serve_paged` and `serve_contiguous` are the pieces `main` is made
of; chip_smoke.py drives the same ones.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.dist import policy as dist_policy
from repro.dist.sharding import SERVE_LAYOUTS, use_rules
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models import build_model
from repro.models.config import ShapeConfig


def pick_layout(model, mesh, *, batch: int, seq_len: int,
                layout: str = "auto", cache: str = "auto"):
    """Resolve the serve (weight layout, cache spec): the policy's
    analytic product decision for "auto"/"auto", else the named layout
    and/or CacheSpec (the full candidate table is still computed so the
    caller can log headroom)."""
    shape = ShapeConfig("serve", "decode", seq_len, batch)
    decision = dist_policy.analytic_serve_decision(
        model, shape, mesh,
        budget_bytes=dist_policy.device_hbm_bytes(mesh.devices.flat[0]))
    if cache != "auto" and model.supports_cache_spec:
        from repro.models.cache import CacheSpec
        cache = CacheSpec.parse(cache).name
    if layout == "auto" and cache == "auto":
        return decision
    cands = [e for e in decision.evals
             if (layout == "auto" or e.layout == layout)
             and (cache == "auto" or e.cache == cache)
             and not e.chunked]
    if not cands:
        # a spec outside the candidate table (e.g. "ring:2/int8"):
        # evaluate the forced combination directly
        cands = [dist_policy.analytic_eval(
            model, shape, mesh,
            layout if layout != "auto" else decision.layout,
            cache_spec=None if cache == "auto" else cache)]
    cap = decision.budget_bytes * decision.margin
    fits = [e for e in cands if e.hbm_bytes <= cap]
    best = min(fits or cands, key=lambda e: e.step_time_s)
    if best.key != decision.key:
        decision = dataclasses.replace(
            decision, layout=best.layout, cache_spec=best.cache,
            chunked=best.chunked, fits=bool(fits),
            evals=decision.evals + tuple(
                e for e in cands if e not in decision.evals),
            reason=f"forced layout={layout} cache={cache} (policy "
                   f"preferred {decision.key}: {decision.reason})")
    return decision


def load(cfg, *, batch: int, seq_len: int, layout: str = "auto",
         cache: str = "auto", seed: int = 0, params=None):
    """Build the model for `cfg`, init its params from `seed` (or take
    `params`, which no cache spec changes) and pick the serve (layout,
    cache spec).  Returns (model, params, decision); the model carries
    the chosen cache spec."""
    model = build_model(cfg)
    if params is None:
        params = model.init(jax.random.key(seed))
    decision = pick_layout(model, make_host_mesh(), batch=batch,
                           seq_len=seq_len, layout=layout, cache=cache)
    if (model.supports_cache_spec and decision.cache_spec
            and decision.cache_spec != cfg.cache_spec):
        model = build_model(dataclasses.replace(
            cfg, cache_spec=decision.cache_spec))
    print(f"[serve] layout={decision.layout}"
          + (f" cache={decision.cache_spec}" if decision.cache_spec else "")
          + f" (peak {decision.chosen.hbm_bytes/1e9:.2f} GB/dev, "
          f"headroom {decision.headroom_bytes()/1e9:.2f} GB) "
          f"-- {decision.reason}")
    return model, params, decision


def serve_paged(model, params, requests, *, max_batch: int,
                block_size: int, num_blocks: int, layout: str,
                max_seq_len: int | None = None):
    """Serve `requests` through PagedServeLoop; at most `max_batch` run at
    once, so later ones join mid-flight.  `max_seq_len` bounds a request's
    prompt + output tokens and the width of the block tables.  Returns
    (finished requests, loop, wall seconds)."""
    from repro.launch.serve_loop import PagedServeLoop
    loop = PagedServeLoop(model, params, max_batch=max_batch,
                          num_blocks=num_blocks, block_size=block_size,
                          chunk=max(block_size * 4, 32), layout=layout,
                          max_seq_len=max_seq_len)
    for r in requests:
        loop.submit(r)
    t0 = time.time()
    done = loop.run_until_drained()
    return done, loop, time.time() - t0


def serve_contiguous(model, params, batch, *, gen: int, rules):
    """Prefill `batch` in one shot, then decode `gen - 1` greedy steps
    with the contiguous cache.  Returns ((B, gen) tokens, prefill
    seconds, decode seconds)."""
    prefill = jax.jit(make_prefill_step(model))
    decode = jax.jit(make_decode_step(model))
    B, T = batch["tokens"].shape
    # the rules must be ambient while the steps TRACE (first call)
    with use_rules(rules):
        t0 = time.time()
        nxt, cache = prefill(params, batch)
        jax.block_until_ready(nxt)
        t_prefill = time.time() - t0
        out = [np.asarray(nxt)]
        t0 = time.time()
        for i in range(gen - 1):
            nxt, cache = decode(params, {
                "tokens": nxt[:, None].astype(jnp.int32),
                "positions": jnp.full((B, 1), T + i, jnp.int32)}, cache)
            out.append(np.asarray(nxt))
        jax.block_until_ready(nxt)
        t_dec = time.time() - t0
    return np.stack(out, axis=1), t_prefill, t_dec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="falcon-mamba-7b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--layout", default="auto",
                    choices=["auto"] + sorted(SERVE_LAYOUTS))
    ap.add_argument("--cache", default="auto",
                    help="KV-cache spec 'layout[:shards]/dtype' (e.g. "
                         "ring:4/int8, head/bf16); 'auto' lets the "
                         "policy pick (models/cache.py)")
    ap.add_argument("--paged", action="store_true",
                    help="serve through the block-table paged "
                         "continuous-batching loop (PagedServeLoop) "
                         "instead of the fixed-batch prefill+decode path")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="KV block pool size (default: sized so the pool "
                         "covers batch x (prompt+gen))")
    ap.add_argument("--max-seq-len", type=int, default=None,
                    help="paged: the most prompt + output tokens of a "
                         "request, which bounds the block tables' width "
                         "(default: a table may span the whole pool)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    B, T = args.batch, args.prompt_len
    model, params, decision = load(cfg, batch=B, seq_len=T + args.gen,
                                   layout=args.layout, cache=args.cache,
                                   seed=args.seed)
    rng = np.random.default_rng(args.seed)
    if args.paged:
        from repro.launch.serve_loop import Request
        nb = args.num_blocks or -(-(B * (T + args.gen) + args.block_size)
                                  // args.block_size)
        requests = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, T)
                            .astype(np.int32), max_new=args.gen)
                    for i in range(2 * B)]   # oversubscribe: mid-flight joins
        done, loop, wall = serve_paged(model, params, requests, max_batch=B,
                                       block_size=args.block_size,
                                       num_blocks=nb, layout=decision.layout,
                                       max_seq_len=args.max_seq_len)
        toks = sum(len(r.out) for r in done)
        print(f"[serve] paged loop: {len(done)} reqs, {toks} tokens in "
              f"{wall*1e3:.1f}ms ({toks/max(wall,1e-9):.0f} tok/s); "
              f"pool {nb}x{args.block_size}, "
              f"shared {loop.alloc.stats['shared_blocks']} blocks; "
              f"counters {loop.counters}")
        print(f"[serve] sample generations (first 12 ids): "
              f"{[r.out[:12] for r in done[:4]]}")
        return

    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)}
    if cfg.frontend == "vision_stub":
        batch["patch_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.frontend_len, cfg.d_model)), jnp.bfloat16)
    if cfg.is_encdec:
        batch["frames"] = jnp.asarray(
            rng.normal(size=(B, T, cfg.d_model)), jnp.bfloat16)
    toks, t_prefill, t_dec = serve_contiguous(model, params, batch,
                                              gen=args.gen,
                                              rules=decision.rules)
    print(f"[serve] prefill {B}x{T}: {t_prefill*1e3:.1f}ms "
          f"({B*T/t_prefill:.0f} tok/s)")
    print(f"[serve] decode {args.gen} steps: {t_dec*1e3:.1f}ms "
          f"({B*(args.gen-1)/max(t_dec,1e-9):.0f} tok/s)")
    print(f"[serve] sample generations (first 12 ids): {toks[:, :12].tolist()}")


if __name__ == "__main__":
    main()
