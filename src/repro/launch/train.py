"""Federated training launcher (Tier B semantics, any scale).

P islands (vmapped, leading island axis), E local steps between weight
exchanges, the exchange as one mixing collective, straggler-driven
selection, int8 compression, checkpoints + resume.  The island axis is
placed on the `pod` axis of a mesh over the attached devices
(`island_mesh`): with 4 chips and 4 islands each island owns a chip; with
one device (the CPU tests) every island is stacked on it.

  PYTHONPATH=src python -m repro.launch.train --arch granite-20b --smoke \
      --steps 60 --islands 2 --local-steps 5 --ckpt-dir ckpt/flight
"""
from __future__ import annotations

import argparse
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, get_smoke_config
from repro.core import aggregation
from repro.core import faults as faults_mod
from repro.core import federated as fed
from repro.data.synthetic import batch_token_stream, make_token_stream
from repro.dist.sharding import ISLAND_RULES, use_rules
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_fl_aggregate, make_fl_train_step
from repro.models import build_model
from repro.optim import adamw, cosine_warmup


def island_mesh(n_islands: int, devices=None):
    """(pod, data, model) mesh over `devices` (default: all attached) with
    as many islands per pod slot as divide evenly: pod = gcd(islands,
    devices), the remaining devices go to "data"."""
    devices = list(devices if devices is not None else jax.devices())
    pod = math.gcd(max(n_islands, 1), len(devices))
    return make_mesh((pod, len(devices) // pod, 1), ("pod", "data", "model"),
                     devices=devices)


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-20b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--islands", type=int, default=2)
    ap.add_argument("--local-steps", type=int, default=4,
                    help="E: train steps between FL exchanges")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compress", nargs="?", const="q8", default="none",
                    choices=["none", "q8", "topk", "q8-topk"],
                    help="delta compression on the exchange (bare flag "
                         "keeps the old int8 behaviour = q8)")
    ap.add_argument("--topk-frac", type=float, default=0.05,
                    help="kept fraction for the topk compression modes")
    ap.add_argument("--overlap", action="store_true",
                    help="double-buffer the exchange: dispatch round r's "
                         "mixing collective, run round r+1's first local "
                         "step concurrently, then merge (1-step-stale "
                         "exchange; federated.fl_overlap_merge)")
    ap.add_argument("--fog-cells", type=int, default=1,
                    help="two-tier exchange: islands aggregate within fog "
                         "cells, then across cells (== flat for matching "
                         "weights; core/hierarchy.py)")
    ap.add_argument("--straggler-slack", type=float, default=3.0)
    ap.add_argument("--byzantine", type=float, default=0.0,
                    help="fraction of islands that ship corrupted updates "
                         "into every exchange (seeded faults.FaultPlan)")
    ap.add_argument("--byzantine-attacks", default="sign_flip,scale",
                    help="comma list from faults.ATTACKS")
    ap.add_argument("--byzantine-scale", type=float, default=10.0)
    ap.add_argument("--robust-agg", default="none",
                    choices=("none",) + aggregation.ROBUST_METHODS,
                    help="swap the weighted mixing collective for a "
                         "Byzantine-robust fold of the island models")
    ap.add_argument("--trim-frac", type=float, default=0.2)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def main(argv=None):
    enable_compile_cache()
    run(parse_args(argv))


def run(args, *, cfg=None, devices=None) -> dict:
    """Train as `args` say; returns {"losses": per-step mean loss,
    "params": final params}.  `cfg` overrides the --arch lookup and
    `devices` the attached devices the island mesh is built from."""
    if cfg is None:
        cfg = get_smoke_config(args.arch) if args.smoke else \
            get_config(args.arch)
    model = build_model(cfg)
    mesh = island_mesh(args.islands, devices)
    with use_rules(ISLAND_RULES), jax.set_mesh(mesh):
        return _train(args, model, mesh)


def _train(args, model, mesh) -> dict:
    cfg = model.cfg
    P = args.islands
    compress = args.compress.replace("-", "_")
    opt = adamw(cosine_warmup(args.lr, 10, args.steps))
    step = jax.jit(make_fl_train_step(model, opt, P))
    agg = jax.jit(make_fl_aggregate(compress=compress,
                                    k_frac=args.topk_frac))
    merge = jax.jit(fed.fl_overlap_merge)
    clock = fed.IslandClock(P)

    params = model.init(jax.random.key(args.seed))
    opt_state = opt.init(params)
    islands = NamedSharding(mesh, PartitionSpec("pod") if P > 1
                            else PartitionSpec())
    if P > 1:
        params = fed.stack_islands(params, P)
        opt_state = fed.stack_islands(opt_state, P)
    params, opt_state = jax.device_put((params, opt_state), islands)

    plan = None
    if args.byzantine > 0 and P > 1:
        plan = faults_mod.FaultPlan(faults_mod.FaultConfig(
            byzantine_frac=args.byzantine,
            attacks=tuple(args.byzantine_attacks.split(",")),
            scale_factor=args.byzantine_scale, seed=args.seed))
        print(f"[train] byzantine islands: {plan.byzantine_in(range(P))}")

    base_params = jax.tree.map(lambda x: x, params)  # last-sync base
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if mgr and args.resume and mgr.latest_step() is not None:
        start, params, opt_state, extra = mgr.restore(
            params_like=params, opt_state_like=opt_state)
        params, opt_state = jax.device_put((params, opt_state), islands)
        print(f"[train] resumed from step {start}")

    streams = [make_token_stream(cfg.vocab_size, 400_000, seed=args.seed + i)
               for i in range(P)]
    n_data = np.array([len(s) for s in streams], np.float64)

    def batch_at(s):
        xs, ys = [], []
        for i in range(P):
            x, y = batch_token_stream(streams[i], args.batch, args.seq, s)
            xs.append(x)
            ys.append(y)
        b = {"tokens": np.stack(xs), "labels": np.stack(ys)}
        if P == 1:
            b = jax.tree.map(lambda v: v[0], b)
        return jax.device_put(b, islands)

    def dispatch_exchange(cur_params, sel):
        """Issue this round's mixing collective (async under jax dispatch).
        Returns (mixed_params | None, tag)."""
        w = (n_data / n_data.sum()) * sel
        if w.sum() <= 0:               # nobody selected -> no exchange
            return None, "no-exchange"
        if args.fog_cells > 1:
            # edge->fog->cloud: two narrow mixing hops instead of one
            # P-wide collective (identical result; tests/test_hierarchy).
            # With compression the edge hop's collective is CELL-LOCAL.
            from repro.core import hierarchy
            cell_of = np.arange(P) % args.fog_cells
            mixed = hierarchy.hierarchical_sync_aggregate(
                cur_params, w, cell_of, compress=compress,
                base_params=base_params if compress != "none" else None,
                k_frac=args.topk_frac)
            tag = f"fog-exchange x{args.fog_cells}"
        else:
            M = jnp.asarray(
                fed.selection_mixing(n_data / n_data.sum(), sel),
                jnp.float32)
            if compress != "none":
                mixed = agg(cur_params, base_params, M)
            else:
                mixed = agg(cur_params, M)
            tag = "exchange"
        if compress != "none":
            tag += f"+{args.compress}"
        return mixed, tag

    def robust_exchange(cur_params, ok: np.ndarray):
        """Byzantine-robust fold of the finite island models; every island
        receives the fold (no mixing matrix an attacker could dominate).
        With --compress the members are first round-tripped through the
        compressed delta wire (per-island payloads) and the quarantine
        gate re-runs on the DECOMPRESSED deltas -- the fold must see and
        threshold what the wire carries, not full-precision local
        weights."""
        tag = f"robust-exchange:{args.robust_agg}"
        if compress != "none":
            from repro.core import compression as comp
            cur_params = comp.roundtrip_islands(
                cur_params, base_params, mode=compress,
                k_frac=args.topk_frac)
            ok = ok & np.asarray(faults_mod.finite_members(cur_params))
            tag += f"+{args.compress}"
        keep = np.flatnonzero(ok)
        if keep.size == 0:
            return None, "no-exchange"
        sub = jax.tree.map(lambda l: l[np.asarray(keep)], cur_params)
        kw = dict(trim_frac=args.trim_frac,
                  base=fed.island_slice(base_params, 0))
        if args.fog_cells > 1:
            from repro.core import hierarchy
            agg_t = hierarchy.hierarchical_robust_aggregate(
                sub, keep % args.fog_cells, args.robust_agg, **kw)
        else:
            agg_t = aggregation.robust_aggregate_stacked(
                sub, args.robust_agg, **kw)
        mixed = jax.tree.map(
            lambda a, l: jnp.broadcast_to(a.astype(l.dtype)[None], l.shape),
            agg_t, cur_params)
        return mixed, tag

    def exchange_input(cur_params, rnd: int):
        """What the aggregator SEES: Byzantine islands corrupt their update
        on the wire (honest islands' local state is never touched)."""
        if plan is None:
            return cur_params, np.ones(P, bool)
        out = cur_params
        for i in plan.byzantine_in(range(P)):
            sub = plan.corrupt(fed.island_slice(out, i),
                               fed.island_slice(base_params, i), i, rnd)
            out = jax.tree.map(lambda l, c: l.at[i].set(c), out, sub)
        # sanitization gate: a non-finite update never reaches the fold.
        # Zero selection weight is NOT enough for the weighted collective
        # (0 * nan = nan in the tensordot), so the rejected islands'
        # slices are also replaced by their last-sync base.
        ok = faults_mod.finite_members(out)
        if not ok.all():
            bad = jnp.asarray(~ok)
            out = jax.tree.map(
                lambda l, b: jnp.where(
                    bad.reshape((-1,) + (1,) * (l.ndim - 1)), b, l),
                out, base_params)
        return out, ok

    pending = None   # (mixed, snapshot) while an overlapped exchange flies
    losses = []
    for s in range(start, args.steps):
        t0 = time.time()
        params, opt_state, metrics = step(params, opt_state, batch_at(s))
        tag = "local"
        if pending is not None:
            # round r's collective was in flight during this step (it ran
            # from the snapshot): fold the exchange correction in without
            # recomputing the step (1-step-stale exchange)
            mixed, snap = pending
            params = merge(params, mixed, snap)
            base_params = mixed
            pending = None
            tag = "local+merge"
        jax.block_until_ready(metrics["loss"])
        dt = time.time() - t0
        clock.observe(np.full(P, dt))  # per-island step times (uniform on CPU)
        loss = float(np.asarray(metrics["loss"]).mean())
        losses.append(loss)
        if (s + 1) % args.local_steps == 0 and P > 1:
            sel = clock.selection(args.straggler_slack)
            ex_in, ok = exchange_input(params, (s + 1) // args.local_steps)
            if args.robust_agg != "none":
                mixed, tag = robust_exchange(ex_in, ok)
            else:
                mixed, tag = dispatch_exchange(ex_in, sel * ok)
            if mixed is None:
                pass
            elif args.overlap and s + 1 < args.steps:
                pending = (mixed, params)  # merge lands after next step
                tag += "+overlap"
            else:
                params = mixed
                base_params = mixed
        print(f"[train] step={s+1} loss={loss:.4f} {dt*1e3:.0f}ms {tag}",
              flush=True)
        if mgr and (s + 1) % args.ckpt_every == 0:
            mgr.save(s + 1, params=params, opt_state=opt_state,
                     extra={"arch": args.arch, "islands": P})
            print(f"[train] checkpoint @ {s+1}")
    print("[train] done")
    return {"losses": losses, "params": params}


if __name__ == "__main__":
    main()
