import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# A CPU AOT tool: pin the CPU platform for this process and the per-cell
# children it starts, so none of them takes an attached TPU.
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ both MUST precede any jax import: jax locks the device count and the
# platform on first init.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Per cell this AOT-compiles (no device allocation beyond host placeholders):
  train_4k              -> the FL island train step (vmapped over pods on the
                           multi-pod mesh) AND the fl_aggregate exchange
  prefill_32k           -> serve prefill step
  decode_32k / long_500k-> serve decode step (KV/state cache as inputs)
and records memory_analysis / cost_analysis / per-collective traffic into
artifacts/dryrun/<arch>__<shape>__<mesh>.json for the roofline tables.

Serve cells (prefill/decode) AOT-compile every candidate weight layout
(stationary / hybrid / fsdp, see dist/sharding.SERVE_LAYOUTS) under the
config's own cache spec and let repro.dist.policy pick one from the XLA
memory_analysis numbers with headroom-aware scoring.  When NO baseline
candidate fits, the policy walks the analytic (weight layout x cache
spec) product frontier (ring-sharded / int8 caches, chunked prefill; see
models/cache.py) best-first, compiling candidates until one is
XLA-verified under budget -- so the chosen candidate is always backed by
a real memory_analysis, never an analytic estimate.  The decision
(chosen layout + cache spec, per-candidate peak HBM, headroom, reason)
lands in the artifact under "layout_decision"; cache-carrying entries
also record cache_bytes_analytic next to an XLA-derived counterpart for
the calibration pin in tests/test_cache_spec.py.

Usage:
  python -m repro.launch.dryrun --arch mixtral-8x22b --shape train_4k --mesh multi
  python -m repro.launch.dryrun --all [--mesh both] [--force]
  python -m repro.launch.dryrun --check-fit --mesh both   # analytic CI gate
"""

import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / \
    os.environ.get("REPRO_DRYRUN_DIR", "dryrun")


def _cell_path(arch: str, shape: str, mesh: str) -> Path:
    return ARTIFACTS / f"{arch}__{shape}__{mesh}.json"


# ---------------------------------------------------------------------------
# In-process lowering of one cell
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, mesh_kind: str,
             overrides: dict | None = None) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core import federated
    from repro.dist import hlo_analysis as H
    from repro.dist import hlo_cost
    from repro.dist import policy as dist_policy
    from repro.dist.sharding import (DEFAULT_RULES, ISLAND_RULES,
                                     serve_layout_rules, spec_tree_for,
                                     use_rules)
    from repro.launch import steps as S
    from repro.launch.mesh import make_production_mesh, n_islands
    from repro.models import build_model
    from repro.models.config import SHAPES
    from repro.models.param import ParamDef, abstract_params, is_def, pdef
    from repro.optim import adamw, opt_state_defs

    overrides = dict(overrides or {})
    # layout override for sweeps: "auto" (policy decides), or a layout
    # name from sharding.SERVE_LAYOUTS.  Legacy `_serve_rules: False`
    # means "force the FSDP training layout".
    forced_layout = overrides.pop("_layout", None)
    if forced_layout == "auto":
        forced_layout = None          # explicit "auto" = policy decides
    if not overrides.pop("_serve_rules", True):
        forced_layout = forced_layout or "fsdp"
    cfg = get_config(arch)
    if overrides:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, **overrides)
    model = build_model(cfg)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    P = n_islands(mesh)

    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "mesh_shape": dict(mesh.shape), "status": "ok",
        "n_params": model.n_params, "n_active_params": model.n_active_params,
        "overrides": overrides or {},
        "entries": {},
    }

    if shape_name == "long_500k" and not cfg.sub_quadratic:
        result["status"] = "skipped"
        result["reason"] = ("full quadratic attention at 524288 tokens; "
                            "long-context runs only for ssm/hybrid/windowed "
                            "archs (DESIGN.md SS6)")
        return result

    def specs(defs, rules):
        return spec_tree_for(defs, mesh, rules)

    def lower_entry(name, fn, in_shardings, args, donate=(), rules=DEFAULT_RULES):
        t0 = time.time()
        jitted = jax.jit(fn, in_shardings=in_shardings,
                         donate_argnums=donate)
        with use_rules(rules), jax.set_mesh(mesh):  # constrain() resolves
            lowered = jitted.lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        flops_xla, byts_xla = H.cost_analysis_terms(compiled)
        txt = compiled.as_text()
        # PRIMARY: trip-count-aware HLO cost model (XLA's cost_analysis
        # counts scan bodies once; see dist/hlo_cost.py + EXPERIMENTS.md).
        hc = hlo_cost.analyze(txt)
        roof = H.Roofline(hc["flops"], hc["hbm_bytes"],
                          hc["collective_bytes"])
        entry = {
            "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
            "hlo_cost": {k: hc[k] for k in
                         ("flops", "hbm_bytes", "collective_bytes",
                          "collective_by_op", "transcendentals")},
            "hlo_cost_diagnostics": hc["diagnostics"][:20],
            "xla_cost_analysis_once": {"flops": flops_xla,
                                       "bytes_accessed": byts_xla},
            "collectives_once": H.collective_bytes(txt),
            "memory_analysis": H.memory_analysis_dict(compiled),
            "roofline": roof.as_dict(),
            "hlo_lines": txt.count("\n"),
        }
        result["entries"][name] = entry
        return entry

    if shape.kind == "train":
        p_defs = model.param_defs()
        o_defs = opt_state_defs(p_defs)
        in_defs = model.input_defs(shape)
        opt = adamw(1e-4)
        if P > 1:
            from repro.models.param import stack_defs as _stack

            def island_stack(defs):
                return jax.tree.map(
                    lambda d: ParamDef((P,) + d.shape, d.dtype,
                                       ("island",) + d.logical_axes,
                                       d.init, d.fan_in_axes),
                    defs, is_leaf=is_def)

            p_defs = island_stack(p_defs)
            o_defs = island_stack(o_defs)
            in_defs = jax.tree.map(
                lambda d: ParamDef((P, d.shape[0] // P) + d.shape[1:],
                                   d.dtype, ("island",) + d.logical_axes,
                                   d.init, d.fan_in_axes),
                in_defs, is_leaf=is_def)
        step = S.make_fl_train_step(model, opt, P)
        args = (abstract_params(p_defs), abstract_params(o_defs),
                abstract_params(in_defs))
        shardings = (specs(p_defs, ISLAND_RULES), specs(o_defs, ISLAND_RULES),
                     specs(in_defs, ISLAND_RULES))
        lower_entry("train_step", step, shardings, args, donate=(0, 1),
                    rules=ISLAND_RULES)

        if P > 1:
            agg = S.make_fl_aggregate()
            mix_def = pdef((P, P), (None, None), dtype=jnp.float32)
            agg_args = (abstract_params(p_defs),
                        jax.ShapeDtypeStruct((P, P), jnp.float32))
            agg_shard = (specs(p_defs, DEFAULT_RULES),
                         specs({"m": mix_def}, DEFAULT_RULES)["m"])
            lower_entry("fl_aggregate", agg, agg_shard, agg_args, donate=(0,))
            # beyond-paper: int8-delta compressed exchange (wire = q8+scales)
            aggc = S.make_fl_aggregate(compress=True)
            aggc_args = (abstract_params(p_defs), abstract_params(p_defs),
                         jax.ShapeDtypeStruct((P, P), jnp.float32))
            aggc_shard = (agg_shard[0], agg_shard[0], agg_shard[1])
            lower_entry("fl_aggregate_q8", aggc, aggc_shard, aggc_args,
                        donate=(0,))
            # analytic bytes-on-wire per exchange round (whole stacked
            # tree, all islands).  The q8 wire counts follow
            # compression.compressed_bytes: blockwise includes the
            # block-multiple PAD the wire actually carries; rowwise is
            # the sharding-preserving layout fl_aggregate_q8 ships.
            from repro.core import compression as _comp
            sds = abstract_params(p_defs)
            result["entries"]["fl_aggregate"]["wire_bytes_analytic"] = {
                "raw_storage": _comp.compressed_bytes(sds, mode="none")}
            result["entries"]["fl_aggregate_q8"]["wire_bytes_analytic"] = {
                "q8_rowwise": _comp.compressed_bytes(sds, mode="q8_rowwise"),
                "q8_wire_blockwise": _comp.compressed_bytes(sds, mode="q8"),
                "q8_topk_wire": _comp.compressed_bytes(sds, mode="q8_topk"),
            }
        else:
            result["entries"]["fl_aggregate"] = {
                "note": "single island on the single-pod mesh: the exchange "
                        "is an identity; lowered on the multi-pod mesh"}

    else:  # prefill / decode: (weight layout x cache spec) product,
        #       picked by repro.dist.policy from XLA memory_analysis
        import dataclasses as _dc
        from repro.dist.sharding import SERVE_LAYOUTS
        p_defs = model.param_defs()
        in_defs = model.input_defs(shape)
        B, Sq = shape.global_batch, shape.seq_len
        base = "prefill_step" if shape.kind == "prefill" else "decode_step"
        spec_capable = (model.supports_cache_spec
                        and model._cache_defs is not None)

        def _sharded(defs, rules):
            return dist_policy.sharded_bytes(defs, mesh, rules)

        def probe(layout, cache_spec=None, chunked=False):
            """AOT-compile the step under one (layout, cache spec
            [, chunked]) candidate; the policy scores the XLA
            memory_analysis + hlo_cost roofline.  cache_spec=None keeps
            the config's own spec (the baseline probes)."""
            rules = serve_layout_rules(layout)
            m = model if not cache_spec else \
                build_model(_dc.replace(cfg, cache_spec=cache_spec))
            if chunked:
                C = dist_policy.CHUNK_TOKENS
                step = S.make_chunk_prefill_step(m)
                ch_in = {
                    "tokens": pdef((B, C), ("batch", None), dtype=jnp.int32),
                    "positions": pdef((B, C), ("batch", None),
                                      dtype=jnp.int32),
                    "last_index": pdef((B,), ("batch",), dtype=jnp.int32),
                }
                c_defs = m.cache_defs(B, Sq)
                all_defs, donate = (p_defs, ch_in, c_defs), (2,)
            elif shape.kind == "prefill":
                step = S.make_prefill_step(m)
                all_defs, donate = (p_defs, in_defs), ()
            else:
                c_defs = m.cache_defs(B, Sq)
                step = S.make_decode_step(m)
                all_defs, donate = (p_defs, in_defs, c_defs), (2,)
            args = tuple(abstract_params(d) for d in all_defs)
            ev_key = layout + (f"+{cache_spec}" if cache_spec else "") + \
                ("+chunked" if chunked else "")
            entry = lower_entry(f"{base}@{ev_key}", step,
                                tuple(specs(d, rules) for d in all_defs),
                                args, donate=donate, rules=rules)
            # analytic cache bytes + an XLA-derived counterpart for the
            # 2x calibration pin (tests/test_cache_spec.py): decode
            # carries the cache as a donated ARGUMENT, one-shot prefill
            # RETURNS it as a non-aliased output.
            ma = entry["memory_analysis"]
            if len(all_defs) == 3:
                entry["cache_bytes_analytic"] = _sharded(all_defs[2], rules)
                entry["cache_bytes_xla_derived"] = max(
                    ma.get("argument_size_in_bytes", 0)
                    - _sharded(p_defs, rules) - _sharded(all_defs[1], rules),
                    0.0)
            elif spec_capable:
                entry["cache_bytes_analytic"] = \
                    _sharded(m.cache_defs(B, Sq), rules)
                entry["cache_bytes_xla_derived"] = max(
                    ma.get("output_size_in_bytes", 0)
                    - ma.get("alias_size_in_bytes", 0), 0.0)
            return dist_policy.eval_from_compiled(
                layout, ma, entry["roofline"],
                cache=cache_spec or "", chunked=chunked)

        if forced_layout:
            probe(forced_layout)
            result["entries"][base] = \
                result["entries"].pop(f"{base}@{forced_layout}")
            result["layout_decision"] = {"layout": forced_layout,
                                         "reason": "forced by override"}
        else:
            # baseline probes: the 3 weight layouts under the config's own
            # cache spec (today's table when everything fits)
            evals = [probe(layout) for layout in SERVE_LAYOUTS]
            decision = dist_policy.decide(evals)
            if not decision.fits and spec_capable:
                # walk the analytic (layout x cache spec) frontier,
                # compiling candidates best-first until one is
                # XLA-verified to fit (bounded tries); skip the head/bf16
                # one-shot candidates -- that IS the baseline convention
                # already compiled above.
                cap = decision.budget_bytes * decision.margin
                cands = [
                    (lo, cs, ch) for (lo, cs, ch)
                    in dist_policy.serve_product_candidates(model, shape)
                    if cs is not None
                    and not (cs == "head/bf16" and not ch
                             and cfg.cache_spec in ("auto", "head/bf16"))]
                scored = sorted(
                    ((dist_policy.analytic_eval(
                        model, shape, mesh, lo, cache_spec=cs, chunked=ch),
                      lo, cs, ch) for lo, cs, ch in cands),
                    key=lambda t: (t[0].hbm_bytes > cap, t[0].step_time_s))
                for _, lo, cs, ch in scored[:6]:
                    ev = probe(lo, cache_spec=cs, chunked=ch)
                    evals.append(ev)
                    if ev.hbm_bytes <= cap:
                        break
                decision = dist_policy.decide(evals)
            result["layout_decision"] = decision.as_dict()
            # canonical entry = the chosen probe; losing probes stay only
            # as compact evals inside layout_decision["candidates"]
            result["entries"][base] = \
                result["entries"].pop(f"{base}@{decision.key}")
            for k in [k for k in result["entries"]
                      if k.startswith(base + "@")]:
                del result["entries"][k]

    return result


# ---------------------------------------------------------------------------
# Driver: one subprocess per cell (isolates the 512-device env + memory)
# ---------------------------------------------------------------------------

def all_cells(meshes=("single", "multi")) -> list[tuple[str, str, str]]:
    from repro.configs import list_archs
    from repro.models.config import SHAPES
    cells = []
    for arch in list_archs():
        for shape in SHAPES:
            for mesh in meshes:
                cells.append((arch, shape, mesh))
    return cells


def check_fit(meshes=("single", "multi")) -> int:
    """Analytic-only CI gate: every serve cell must have >=1 fitting
    (weight layout x cache spec) product.  Runs on AbstractMesh (no
    512-device env, no compiles) so the CI scale job can assert coverage
    in seconds; the compiled sweep is the ground truth behind it."""
    from repro.configs import get_config, list_archs
    from repro.dist import policy as dist_policy
    from repro.launch.mesh import abstract_production_mesh
    from repro.models import build_model
    from repro.models.config import SHAPES
    bad = []
    for mesh_kind in meshes:
        mesh = abstract_production_mesh(multi_pod=(mesh_kind == "multi"))
        for arch in list_archs():
            cfg = get_config(arch)
            if cfg.family == "cnn":
                continue
            model = build_model(cfg)
            for shape_name, shape in SHAPES.items():
                if shape.kind == "train":
                    continue
                if shape_name == "long_500k" and not cfg.sub_quadratic:
                    continue
                d = dist_policy.analytic_serve_decision(model, shape, mesh)
                print(f"[check-fit] {mesh_kind:6s} {arch:22s} "
                      f"{shape_name:12s} {d.key:30s} "
                      f"peak={d.chosen.hbm_bytes/1e9:7.2f} GB "
                      f"{'ok' if d.fits else 'NO-FIT'}", flush=True)
                if not d.fits:
                    bad.append((arch, shape_name, mesh_kind))
    if bad:
        print(f"[check-fit] {len(bad)} cells with NO fitting "
              f"(layout, cache) product: {bad}", flush=True)
        return 1
    print("[check-fit] every serve cell has >=1 fitting (weight, cache) "
          "layout", flush=True)
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--check-fit", action="store_true",
                    help="analytic-only: assert every serve cell has >=1 "
                         "fitting (weight layout x cache spec) product")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--overrides", default=None,
                    help="JSON dict of ModelConfig overrides (perf sweeps)")
    ap.add_argument("--tag", default=None,
                    help="artifact filename suffix for override sweeps")
    args = ap.parse_args()
    ARTIFACTS.mkdir(parents=True, exist_ok=True)

    if args.check_fit:
        meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
        sys.exit(check_fit(meshes))

    if args.all:
        meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
        cells = all_cells(meshes)
        todo = [c for c in cells if args.force or not _cell_path(*c).exists()]
        print(f"[dryrun] {len(todo)}/{len(cells)} cells to run", flush=True)
        failures = []
        for i, (arch, shape, mesh) in enumerate(todo):
            cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch",
                   arch, "--shape", shape, "--mesh", mesh]
            print(f"[dryrun {i+1}/{len(todo)}] {arch} {shape} {mesh}",
                  flush=True)
            r = subprocess.run(cmd, capture_output=True, text=True)
            if r.returncode != 0:
                failures.append((arch, shape, mesh))
                print(r.stdout[-2000:], r.stderr[-2000:], flush=True)
        print(f"[dryrun] done; {len(failures)} failures: {failures}",
              flush=True)
        sys.exit(1 if failures else 0)

    assert args.arch and args.shape
    overrides = json.loads(args.overrides) if args.overrides else None
    try:
        res = run_cell(args.arch, args.shape, args.mesh, overrides)
    except Exception:
        res = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "status": "error", "traceback": traceback.format_exc()}
    name = f"{args.arch}__{args.shape}__{args.mesh}"
    if args.tag:
        name += f"__{args.tag}"
    out = ARTIFACTS / f"{name}.json"
    out.write_text(json.dumps(res, indent=2, default=str))
    print(json.dumps({k: v for k, v in res.items() if k != "entries"},
                     indent=2, default=str))
    if "layout_decision" in res:
        d = res["layout_decision"]
        cs = d.get("cache_spec", "")
        print(f"  layout={d['layout']}"
              + (f" cache={cs}" if cs else "")
              + (" chunked" if d.get("chunked") else "")
              + f" ({d.get('reason', '')})")
    for ename, e in res.get("entries", {}).items():
        if "roofline" in e:
            r = e["roofline"]
            print(f"  {ename}: dominant={r['dominant']} "
                  f"t_comp={r['t_compute_s']:.2e}s "
                  f"t_mem={r['t_memory_s']:.2e}s "
                  f"t_coll={r['t_collective_s']:.2e}s "
                  f"(lower {e['lower_s']}s compile {e['compile_s']}s)")
    if res["status"] == "error":
        print(res["traceback"][-3000:])
        sys.exit(1)


if __name__ == "__main__":
    main()
