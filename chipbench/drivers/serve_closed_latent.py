"""Closed-loop serving of a latent-attention (MLA) MoE decoder at one chip's
expert share -- Moonlight-16B-A3B's deepseek_v3 blocks -- through the
program's PagedServeLoop.

The clients, the size stream and the window are serve_closed's: set-up
ends with every client's first request submitted and one tick run.  What
differs:
  * the program's config is the file's `program_arch` holding the experts
    `held_experts_first` .. + `n_routed_experts` of the router's
    `n_routed_experts_published`, checked against the file's published
    keys (a program that changed a width stops the run);
  * every weight comes from the seed, checked against the shapes the
    file's keys give; the router's e_score_correction_bias is drawn too
    (non-zero, so the bias path is exercised);
  * the loop's block tables are bounded by the traffic's longest request,
    prompt_max + out_max (the loop's `max_seq_len`);
  * the work is counted by chipbench/latent_flops.py, and the window
    records the loop's counters at its open and close
    (readings["counters"]);
  * the check teacher-forces chipbench/reference/moonlight.py and judges
    the mean gap of the compared tokens (`judge`), limit `logit_gap_mean`.
Traffic-file keys: those of serve_closed.

The readings that set the traffic's limit (the program's compared gaps,
the fp8 control's, and the compared tokens at which a bfloat16 forward of
the reference chooses other experts than the float32 one in some layer),
one JSON line per seed, on the chip:

  python3 -m chipbench.drivers.serve_closed_latent --seeds 1 2 3 \
      [--seconds 20] [--workload serve-moonlight-decode64]
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from chipbench import common, latent_flops, serving, weights
from chipbench.drivers import serve_closed
from chipbench.reference import moonlight as ref

#: fan-in axes of each matrix, counted without the stacked layer axis and,
#: for a routed expert's matrices, without the expert axis
FAN_IN = {"wq": (0,), "wkv_a": (0,), "w_uk": (0,), "w_uv": (0,),
          "wo": (0, 1), "w_up": (0,), "w_gate": (0,), "w_down": (0,),
          "w_router": (0,), "unembed": (0,)}
#: spread of the drawn e_score_correction_bias
E_BIAS_STD = 0.05
#: the program's widest gaps whose tokens' routing flips `readings` counts
WIDEST = 10


def max_seq_len(t: dict) -> int:
    return t["prompt_max"] + t["out_max"]


def program_config(c: dict):
    """The program's ModelConfig at the file's expert share, checked
    against the file's published keys."""
    import dataclasses
    from repro.configs import get_config, get_smoke_config
    get = get_smoke_config if c.get("smoke") else get_config
    cfg = dataclasses.replace(
        get(c["program_arch"]),
        held_experts=(c["held_experts_first"], c["n_routed_experts"]))
    want = {"hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
            "moe_intermediate_size": cfg.moe_d_ff,
            "num_hidden_layers": cfg.num_layers,
            "first_k_dense_replace": cfg.first_dense_layers,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim,
            "n_routed_experts_published": cfg.num_experts,
            "n_routed_experts": cfg.experts_here[1],
            "num_experts_per_tok": cfg.experts_per_token,
            "n_shared_experts": cfg.n_shared_experts,
            "routed_scaling_factor": cfg.routed_scaling,
            "scoring_func": cfg.router, "vocab_size": cfg.vocab_size,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "hidden_act": cfg.act, "attention_bias": cfg.qkv_bias,
            "tie_word_embeddings": cfg.tie_embeddings,
            # the forms the program implements, and no other
            "q_lora_rank": None, "n_group": 1, "topk_group": 1,
            "topk_method": "noaux_tc", "norm_topk_prob": True}
    bad = {k: (c[k], v) for k, v in want.items() if c[k] != v}
    if bad or cfg.capacity_factor > 0:
        raise ValueError(f"program config differs from the file: {bad}")
    return cfg


def expected_shapes(c: dict) -> dict:
    """{path: shape} of the program's parameter tree, from the file's
    published keys (the held experts only)."""
    d, L, k = c["hidden_size"], c["num_hidden_layers"], c["first_k_dense_replace"]
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    F, f, V = c["intermediate_size"], c["moe_intermediate_size"], c["vocab_size"]
    E, Eh, fs = (c["n_routed_experts_published"], c["n_routed_experts"],
                 c["n_shared_experts"] * c["moe_intermediate_size"])
    shapes = {"embed/tok": (V, d), "embed/unembed": (d, V),
              "final_norm/scale": (d,)}
    for stack, n in (("dense_layers", k), ("layers", L - k)):
        s = lambda path, *dims: shapes.__setitem__(f"{stack}/{path}",
                                                   (n,) + dims)
        s("ln1/scale", d)
        s("ln2/scale", d)
        s("attn/wq", d, H, dn + dr)
        s("attn/wkv_a", d, r + dr)
        s("attn/kv_norm/scale", r)
        s("attn/w_uk", r, H, dn)
        s("attn/w_uv", r, H, dv)
        s("attn/wo", H, dv, d)
        if stack == "dense_layers":
            s("mlp/w_up", d, F)
            s("mlp/w_gate", d, F)
            s("mlp/w_down", F, d)
            continue
        s("moe/w_router", d, E)
        s("moe/e_bias", E)
        s("moe/w_gate", Eh, d, f)
        s("moe/w_up", Eh, d, f)
        s("moe/w_down", Eh, f, d)
        s("moe/shared/w_up", d, fs)
        s("moe/shared/w_gate", d, fs)
        s("moe/shared/w_down", fs, d)
    return shapes


def _leaf(key, path: str, shape, dtype):
    """Norm scales 1 + N(0, 0.1^2), the embedding N(0, 1), the bias
    N(0, E_BIAS_STD^2), matrices N(0, 1/fan_in)."""
    import jax
    import jax.numpy as jnp
    name = path.rsplit("/", 1)[-1]
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "scale":
        x = 1.0 + 0.1 * z
    elif name == "e_bias":
        x = E_BIAS_STD * z
    elif name == "tok":
        x = z
    else:
        dims = shape[1:] if "layers/" in path else shape
        if "/moe/w_" in path and name != "w_router":
            dims = dims[1:]                       # the expert axis
        x = z / math.sqrt(math.prod(dims[a] for a in FAN_IN[name]))
    return x.astype(dtype)


def make_weights(c: dict, key, program_tree):
    """The weights as the program's nested tree, every leaf from its own
    fold of `key` in one jit; `program_tree` is the program's abstract
    tree, and a path or shape that differs from the file's is an error."""
    import jax
    want = {"/".join(str(k.key) for k in path): l
            for path, l in jax.tree_util.tree_flatten_with_path(
                program_tree)[0]}
    got = {p: tuple(l.shape) for p, l in want.items()}
    shapes = expected_shapes(c)
    if got != shapes:
        diff = sorted(set(got.items()) ^ set(shapes.items()))
        raise ValueError(f"program parameter tree differs from the "
                         f"configuration file: {diff[:6]}")
    paths = sorted(want)

    def build(key):
        return {p: _leaf(jax.random.fold_in(key, i), p, want[p].shape,
                         want[p].dtype) for i, p in enumerate(paths)}

    return weights.nest(jax.jit(build)(key))


class Server(serving.Server):
    """Set-up: the chip-share config, weights from the seed, the program's
    loop with bounded block tables, warm shapes."""

    def __init__(self, cfgfile: dict, t: dict, seed: int, spans):
        import jax
        from repro.launch import serve
        from repro.launch.serve_loop import Request
        from repro.models import build_model
        self.c = cfgfile
        self.t = t
        cfg = program_config(cfgfile)
        self.vocab = cfg.vocab_size
        with spans("weights"):
            tree = jax.eval_shape(build_model(cfg).init, jax.random.key(0))
            self.weights = make_weights(cfgfile, common.seed_key(seed), tree)
            jax.block_until_ready(self.weights)
        with spans("load"):
            model, self.params, decision = serve.load(
                cfg, batch=t["max_batch"], seq_len=max_seq_len(t),
                params=self.weights)
            self.model, self.layout = model, decision.layout
            self.new_loop()
        self.Request = serving.stamped_request_class(Request)
        with spans("warm"):
            self.warm(np.random.default_rng([seed, 9]))

    def new_loop(self):
        from repro.launch.serve_loop import PagedServeLoop
        pool = self.c["serve_pool"]
        self.loop = PagedServeLoop(
            self.model, self.params, max_batch=self.t["max_batch"],
            num_blocks=pool["num_blocks"], block_size=pool["block_size"],
            layout=self.layout, max_seq_len=max_seq_len(self.t))


class Window(serving.Window):
    """serving.Window, with the loop's counters at the window's open and
    close and the work counted for latent attention."""

    def open(self):
        self.loop_counters = {"open": dict(self.s.loop.counters)}
        super().open()

    def close(self):
        super().close()
        self.loop_counters["close"] = dict(self.s.loop.counters)

    def model_flops(self):
        c = self.s.c
        total = 0.0
        for r in self.reqs:
            T = len(r.prompt)
            for i, t in enumerate(r.rec.t):
                if self.t0 <= t <= self.t_close:
                    total += (latent_flops.prefill(c, T) if i == 0
                              else latent_flops.decode(c, T + i))
        return total


def ref_config(c: dict) -> tuple:
    """The hashable (key, value) pairs the reference reads."""
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, bool, str))))


def compare(server: Server, done, seed: int, control: bool = False):
    """serving.compare against reference/moonlight.py.  Returns (gaps of
    the served tokens, {"fp8": .., "bf16": .., "bf16_stream": ..} gaps of
    the first choices of the reference computed at those precisions or
    None, outputs of the wrong length, for each compared token whether the
    bfloat16 reference chose other experts than the float32 one in some
    layer, or None)."""
    import jax
    import jax.numpy as jnp
    c = ref_config(server.c)
    kw = dict(c=c, held_first=server.c["held_experts_first"])
    prog, bad_len = [], 0
    ctrl, flips = {"fp8": [], "bf16": [], "bf16_stream": []}, []
    k = server.t.get("compare_requests", serving.SAMPLE)
    for r in serving.sample(done, seed, k):
        out = np.asarray(r.out, np.int32)
        bad_len += len(out) != r.max_new
        seq = np.concatenate([r.prompt, out[:-1]])
        T, n = len(seq), len(out)
        tokens = np.zeros(-(-T // serving.PAD) * serving.PAD, np.int32)
        tokens[:T] = seq
        rows = np.full(server.t["out_max"], T - 1, np.int32)
        rows[:n] = np.arange(len(r.prompt) - 1, T)
        tgt = np.zeros(len(rows), np.int32)
        tgt[:n] = out
        args = (server.weights, jnp.asarray(tokens), jnp.asarray(rows))
        lg, routes = ref.forward(*args, **kw)
        prog.append(jax.device_get(ref.gaps(lg, jnp.asarray(tgt)))[:n])
        if control:
            top = jnp.argmax(ref.logits_at(*args, precision="fp8", **kw), 1)
            ctrl["fp8"].append(jax.device_get(ref.gaps(lg, top))[:n])
            lg16, r16 = ref.forward(*args, precision="bf16", **kw)
            ctrl["bf16"].append(jax.device_get(
                ref.gaps(lg, jnp.argmax(lg16, 1)))[:n])
            flips.append(jax.device_get(
                (r16 != routes).any(axis=(0, 2)))[:n])
            top = jnp.argmax(ref.logits_at(*args, precision="bf16_stream",
                                           **kw), 1)
            ctrl["bf16_stream"].append(jax.device_get(ref.gaps(lg, top))[:n])
    if not control:
        return np.concatenate(prog), None, bad_len, None
    return (np.concatenate(prog),
            {k: np.concatenate(v) for k, v in ctrl.items()}, bad_len,
            np.concatenate(flips))


def judge(gaps, wrong_lengths: int, limit: float):
    """(correct, {name: [value, limit]}) of one run's compared tokens: the
    MEAN gap, not serving.judge's mean of the three widest.  Here the
    widest gaps are set by routing flips at near-ties (26 layers choosing
    6 of 64 experts on sigmoid scores): on the chip the program's widest
    three read up to 1.77 and the fp8 control's as low as 1.52, so no
    limit on them lies between; the mean separates them 5x.  None compared
    (nothing finished) is not correct."""
    mean = float(gaps.mean()) if gaps is not None and len(gaps) else None
    numbers = {"logit_gap_mean": [mean, limit],
               "wrong_lengths": [wrong_lengths, 0]}
    return (mean is not None and mean <= limit
            and wrong_lengths == 0), numbers


def check(server: Server, done, seed: int, limit: float):
    """Returns (correct, {name: [value, limit]}, details)."""
    if not done:
        return (*judge(None, 0, limit), {"compared_tokens": 0})
    gaps, _, bad_len, _ = compare(server, done, seed)
    return (*judge(gaps, bad_len, limit),
            {"compared_tokens": len(gaps), "logit_gap_max": float(gaps.max()),
             "logit_gap_top3": serving.widest(gaps)})


def measure(server: Server, seconds, traced, clock, t_start, devs, seed, t,
            prime, drive):
    """serving.measure with this driver's window, readings and check."""
    from chipbench import tracing
    spans = common.Spans(traced)
    w = Window(server, seconds, spans)
    prime(w)
    setup_s = time.perf_counter() - t_start
    compiles = clock.count
    common.say(setup_s=setup_s, compile_s=clock.seconds,
               compiles_in_setup=compiles)
    trace = tracing.Capture(traced)
    with trace:
        w.open()
        with spans("window"):
            drive(w)
            w.close()
    device = common.device_info(devs)
    common.say(**w.counters(), compiles_in_window=clock.count - compiles,
               peak_bytes=device["memory_peak_bytes"],
               bytes_limit=common.bytes_limit(devs),
               loop_counters=w.loop_counters)
    gaps = w.gaps()
    e2e = {"setup_s": setup_s,
           "tokens_per_s": len(w.emitted()) / (w.t_close - w.t0),
           "itl_p99_ms": common.quantile(gaps, 0.99) * 1e3 if gaps else None}
    readings = {"trace": trace.reduce(),
                "occupancy": w.occupancy, "max_batch": t["max_batch"],
                "flops": w.model_flops(), "counters": w.loop_counters,
                "latent_block_bytes": latent_flops.latent_block_bytes(
                    server.c, server.c["serve_pool"]["block_size"])}
    done, attempted = w.done, w.attempted()
    breakdown = trace.breakdown() if traced else None
    if traced:
        device.update(trace.device_fields())
    del w
    server.free_program()
    correct, checks, details = check(server, done, seed,
                                     t["limits"]["logit_gap_mean"])
    common.say(**details)
    return {"e2e": e2e, "readings": readings, "device": device,
            "correct": correct, "attempted": attempted,
            "failed": checks["wrong_lengths"][0], "checks": checks,
            "breakdown": breakdown}


def run(cfgfile, t, *, seed, seconds, traced, clock, t_start, devs):
    server = Server(cfgfile, t, seed, common.Spans(traced))
    return measure(server, seconds, traced, clock, t_start, devs, seed, t,
                   *serve_closed.drive_for(server, t, seed, seconds))


def readings(cfgfile, t, seed: int, seconds: float, devs=None) -> dict:
    """One seed's limit readings: a window of the cell's traffic, then the
    program's gaps, the fp8 control's, those of the reference computed in
    bfloat16 (matmul inputs; and the residual stream too: what rounding
    alone gives), the routing flips, and how many of the program's
    WIDEST widest gaps fall on a token with a flip."""
    server = Server(cfgfile, t, seed, common.Spans(False))
    w = Window(server, seconds, common.Spans(False))
    prime, drive = serve_closed.drive_for(server, t, seed, seconds)
    prime(w)
    w.open()
    drive(w)
    w.close()
    done = w.done
    out = {"seed": seed, "finished": len(done),
           "preemptions": w.loop_counters["close"]["preemptions"]}
    if devs:
        out["peak_bytes"] = common.device_info(devs)["memory_peak_bytes"]
    del w
    server.free_program()
    prog, ctrl, bad, flips = compare(server, done, seed, control=True)
    limit = t["limits"]["logit_gap_mean"]
    widest = np.argsort(prog)[-WIDEST:]
    out.update(compared_tokens=len(prog), wrong_lengths=bad,
               routing_flip_tokens=int(flips.sum()),
               widest_with_flip=int(flips[widest].sum()), widest=len(widest))
    for name, gaps in (("", prog), ("control_fp8_", ctrl["fp8"]),
                       ("bf16_reference_", ctrl["bf16"]),
                       ("bf16_stream_reference_", ctrl["bf16_stream"])):
        out.update({f"{name}logit_gap_top3": serving.widest(gaps),
                    f"{name}logit_gap_max": float(gaps.max()),
                    f"{name}logit_gap_mean": float(gaps.mean()),
                    f"{name}correct": judge(gaps, bad, limit)[0]})
    return out


def main(argv=None) -> int:
    import argparse
    import json
    from chipbench.run import cell_files
    ap = argparse.ArgumentParser(description="limit readings, per seed")
    ap.add_argument("--workload", default="serve-moonlight-decode64")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    cell, cfgfile, t, bench = cell_files(args.workload)
    common.program_path()
    devs = common.require_chip(cell["chips"])
    common.compile_cache()
    for seed in args.seeds:
        row = readings(cfgfile, t, seed, args.seconds or bench["run_seconds"],
                       devs)
        print(json.dumps({"workload": cell["name"], **row}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
