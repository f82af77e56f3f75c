"""What the serve drivers share: set-up of the program's paged serve path,
token stamps, window accounting and the check against the reference.

The window drives the program's `PagedServeLoop` through `submit` and
`tick`, as `launch/serve.py` does, with the loop's own defaults (prefill
chunk included).  Requests are the program's `Request`, with one change
the program cannot see: the list that collects a request's tokens notes
the host time at which each token index is first appended.  The loop
appends a token right after reading it back, so the stamp is when that
token reached the host (a first token as soon as its prefill ends, not at
the end of the tick).  A preempted request starts a new list; tokens it
emits again keep their first stamps, so a replay shows as one long gap."""
from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import common, flops, traffic
from chipbench.reference import qwen as ref

#: requests compared with the reference after the window, unless the
#: traffic file sets `compare_requests`
SAMPLE = 4
#: reference sequence lengths are padded to a multiple of PAD, and the
#: compared rows to the traffic's out_max, so few shapes compile
PAD = 1024
#: widest gaps whose mean is compared: a single widest gap is one token's
#: margin and swings from seed to seed (PERF.md)
TOP = 3


class Emissions:
    def __init__(self, due: float):
        self.due = due
        self.t: list[float] = []       # first stamp of each token index
        self.preempted = 0


class StampedOut(list):
    def __init__(self, rec: Emissions, items=()):
        super().__init__(items)
        self.rec = rec

    def append(self, tok):
        super().append(tok)
        if len(self) > len(self.rec.t):
            self.rec.t.append(time.perf_counter())


def stamped_request_class(Request):
    """The program's Request, whose `out` list stamps first emissions."""

    class StampedRequest(Request):
        def __setattr__(self, name, value):
            if name == "out" and not isinstance(value, StampedOut):
                rec = self.__dict__.get("rec")
                if rec is None:
                    rec = Emissions(0.0)
                    object.__setattr__(self, "rec", rec)
                elif len(rec.t):
                    rec.preempted += 1
                value = StampedOut(rec, value)
            object.__setattr__(self, name, value)

    return StampedRequest


def program_config(cfgfile: dict):
    """The program's ModelConfig for the file's `program_arch`, with the
    file's `rope_theta` (an option of the config), checked against the
    file's published keys (a program that changed a width stops the
    run)."""
    import dataclasses
    from repro.configs import get_config, get_smoke_config
    get = get_smoke_config if cfgfile.get("smoke") else get_config
    cfg = dataclasses.replace(get(cfgfile["program_arch"]),
                              rope_theta=cfgfile["rope_theta"])
    want = {"hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
            "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "vocab_size": cfg.vocab_size, "qkv_bias": cfg.qkv_bias,
            "tie_word_embeddings": cfg.tie_embeddings,
            "rope_theta": cfg.rope_theta, "hidden_act": cfg.act}
    bad = {k: (cfgfile[k], v) for k, v in want.items() if cfgfile[k] != v}
    if bad or cfg.head_dim * cfg.num_heads != cfg.d_model:
        raise ValueError(f"program config differs from the file: {bad}")
    return cfg


class Server:
    """Set-up: weights from the seed, the program's loop, warm shapes."""

    def __init__(self, cfgfile: dict, t: dict, seed: int, spans):
        import jax
        from repro.launch import serve
        from repro.launch.serve_loop import Request
        from repro.models import build_model
        from chipbench import weights
        self.c = cfgfile
        self.t = t
        cfg = program_config(cfgfile)
        self.vocab = cfg.vocab_size
        with spans("weights"):
            tree = jax.eval_shape(build_model(cfg).init, jax.random.key(0))
            self.weights = weights.dense_decoder(
                cfgfile, common.seed_key(seed), tree)
            jax.block_until_ready(self.weights)
        with spans("load"):
            model, self.params, decision = serve.load(
                cfg, batch=t["max_batch"],
                seq_len=t["prompt_max"] + t["out_max"], params=self.weights)
            self.model, self.layout = model, decision.layout
            self.new_loop()
        self.Request = stamped_request_class(Request)
        with spans("warm"):
            self.warm(np.random.default_rng([seed, 9]))

    def new_loop(self):
        """A fresh PagedServeLoop over the cell's pool (drop the old one
        first: two pools do not fit)."""
        from repro.launch.serve_loop import PagedServeLoop
        pool = self.c["serve_pool"]
        self.loop = PagedServeLoop(
            self.model, self.params, max_batch=self.t["max_batch"],
            num_blocks=pool["num_blocks"], block_size=pool["block_size"],
            layout=self.layout)

    def warm(self, rng):
        """Every shape the traffic uses: the decode step at max_batch and
        each prefill bucket (the full chunk and tails of 1..chunk/2)."""
        chunk = self.loop.chunk
        tails = [chunk]
        while tails[-1] > 1:
            tails.append(tails[-1] // 2)
        for i, tail in enumerate(tails):
            n = chunk + (tail if tail < chunk else 0)
            self.loop.submit(self.Request(
                rid=-1 - i, prompt=rng.integers(0, self.vocab, n).astype(
                    np.int32), max_new=2))
        self.loop.run_until_drained()

    def request(self, spec: traffic.Spec, due: float):
        r = self.Request(rid=spec.rid, prompt=spec.prompt,
                         max_new=spec.max_new)
        r.rec.due = due
        return r

    def free_program(self):
        """Drop the loop and its block pool before the reference runs."""
        self.loop = None
        gc.collect()


class Window:
    """Host bookkeeping of one measured window.

    No tick starts after `t_end`; the window closes (`t_close`) when the
    tick running at `t_end` has ended, so every token of the ticks that
    started inside counts, over the time to the close."""

    def __init__(self, server: Server, seconds: float, spans):
        self.s = server
        self.spans = spans
        self.seconds = seconds
        self.reqs = []            # every request submitted, in order
        self.done = []            # finished requests
        self.occupancy = []       # live slots per decode tick
        self.t0 = self.t_end = self.t_close = float("inf")  # until open

    def open(self):
        self.t0 = time.perf_counter()
        self.t_end = self.t0 + self.seconds

    def close(self):
        self.t_close = time.perf_counter()

    def submit(self, spec, due: float):
        r = self.s.request(spec, due)
        self.s.loop.submit(r)
        self.reqs.append(r)
        return r

    def tick(self):
        loop = self.s.loop
        start = time.perf_counter()
        with self.spans("tick"):
            finished = loop.tick()
        live = len(loop.live) + len(finished)
        if live and start >= self.t0:
            self.occupancy.append(live)
        self.done += finished
        return finished

    # -- accounting -----------------------------------------------------
    def attempted(self) -> int:
        """Requests submitted before the window's end."""
        return sum(r.rec.due < self.t_end for r in self.reqs)

    def emitted(self):
        """First-emission stamps of every token inside the window."""
        return [t for r in self.reqs for t in r.rec.t
                if self.t0 <= t <= self.t_close]

    def gaps(self):
        """Gaps between a request's consecutive tokens, both inside."""
        return [b - a for r in self.reqs
                for a, b in zip(r.rec.t, r.rec.t[1:])
                if self.t0 <= a and b <= self.t_close]

    def model_flops(self):
        """Operations the forward pass needs for the work done in the
        window: the prompts first prefilled and the tokens first decoded
        there (replays after a preemption are not needed work)."""
        c = self.s.c
        total = 0.0
        for r in self.reqs:
            T = len(r.prompt)
            for i, t in enumerate(r.rec.t):
                if not self.t0 <= t <= self.t_close:
                    continue
                total += (flops.decoder_prefill(c, T) if i == 0
                          else flops.decoder_decode(c, T + i))
        return total

    def counters(self) -> dict:
        """Earlier-line counts (set-up's requests included)."""
        return {"requests_submitted": len(self.reqs),
                "requests_finished": len(self.done),
                "admissions": sum(bool(r.rec.t) for r in self.reqs),
                "preemptions": sum(r.rec.preempted for r in self.reqs),
                "decode_ticks": len(self.occupancy)}


def sample(done, seed: int, k: int):
    """The finished requests compared: the longest (prompt and output) and
    k - 1 others drawn from the seed."""
    rng = np.random.default_rng([seed, 7])
    longest = max(done, key=lambda r: len(r.prompt) + len(r.out))
    rest = [r for r in done if r is not longest]
    return [longest] + [rest[i] for i in rng.permutation(len(rest))[
        :k - 1]]


def compare(server: Server, done, seed: int, control: bool = False):
    """Teacher-force the float32 reference over each sampled request's
    prompt and served tokens.  Returns (how far each served token's logit
    lies below the reference's best, the same for the token the fp8
    control puts first or None, outputs of the wrong length), the gaps
    over every compared token."""
    import jax
    import jax.numpy as jnp
    c = tuple(sorted((k, v) for k, v in server.c.items()
                     if isinstance(v, (int, float, bool, str))))
    prog, ctrl, bad_len = [], [], 0
    k = server.t.get("compare_requests", SAMPLE)
    for r in sample(done, seed, k):
        out = np.asarray(r.out, np.int32)
        bad_len += len(out) != r.max_new
        seq = np.concatenate([r.prompt, out[:-1]])
        T, n = len(seq), len(out)
        tokens = np.zeros(-(-T // PAD) * PAD, np.int32)
        tokens[:T] = seq
        rows = np.full(server.t["out_max"], T - 1, np.int32)
        rows[:n] = np.arange(len(r.prompt) - 1, T)
        tgt = np.zeros(len(rows), np.int32)
        tgt[:n] = out
        args = (server.weights, jnp.asarray(tokens), jnp.asarray(rows))
        lg = ref.logits_at(*args, c=c)
        prog.append(jax.device_get(ref.gaps(lg, jnp.asarray(tgt)))[:n])
        if control:
            top = jnp.argmax(ref.logits_at(*args, c=c, precision="fp8"), 1)
            ctrl.append(jax.device_get(ref.gaps(lg, top))[:n])
    return (np.concatenate(prog), np.concatenate(ctrl) if control else None,
            bad_len)


def widest(gaps, k: int = TOP) -> float:
    """Mean of the k widest gaps (all of them when fewer)."""
    return float(np.sort(gaps)[-k:].mean())


def judge(gaps, wrong_lengths: int, limit: float):
    """(correct, {name: [value, limit]}) of one run's compared tokens: the
    mean of the TOP widest gaps; none compared (nothing finished) is not
    correct."""
    top = widest(gaps) if gaps is not None and len(gaps) else None
    numbers = {"logit_gap_top3": [top, limit],
               "wrong_lengths": [wrong_lengths, 0]}
    return (top is not None and top <= limit
            and wrong_lengths == 0), numbers


def check(server: Server, done, seed: int, limit: float):
    """Returns (correct, {name: [value, limit]}, details)."""
    if not done:
        return (*judge(None, 0, limit), {"compared_tokens": 0})
    gaps, _, bad_len = compare(server, done, seed)
    return (*judge(gaps, bad_len, limit),
            {"compared_tokens": len(gaps), "logit_gap_max": float(gaps.max()),
             "logit_gap_mean": float(gaps.mean())})


def measure(server: Server, seconds, traced, clock, t_start, devs, seed, t,
            prime, drive):
    """Finish set-up with `prime(window)`, run the window with
    `drive(window)`, then account for it and compare with the
    reference."""
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
               bytes_limit=common.bytes_limit(devs))
    gaps = w.gaps()
    e2e = {"setup_s": setup_s,
           "tokens_per_s": len(w.emitted()) / (w.t_close - w.t0),
           "itl_p99_ms": common.quantile(gaps, 0.99) * 1e3 if gaps else None}
    readings = {"trace": trace.reduce(),
                "occupancy": w.occupancy, "max_batch": t["max_batch"],
                "flops": w.model_flops()}
    done, attempted = w.done, w.attempted()
    breakdown = trace.breakdown() if traced else None
    if traced:
        device.update(trace.device_fields())
    del w
    server.free_program()
    correct, checks, details = check(server, done, seed,
                                     t["limits"]["logit_gap_top3"])
    common.say(**details)
    return {"e2e": e2e, "readings": readings, "device": device,
            "correct": correct, "attempted": attempted,
            "failed": checks["wrong_lengths"][0], "checks": checks,
            "breakdown": breakdown}
