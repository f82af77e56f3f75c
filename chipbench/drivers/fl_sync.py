"""Back-to-back synchronous federated rounds of the paper's CNN.

A round is what the program's own callers do (`core/scenarios.py`,
`launch/train.py` fog mode): `LocalTrainer.train_cohort` over the whole
cohort, then `hierarchy.hierarchical_sync_aggregate(compress="q8")`
against the stacked global model, called eagerly, and member 0 of the
fold is the new global model.  A round completes when those params are
ready on the device.

Set-up makes the data and weights from the seed and runs rounds 1-3,
which compile every program and are the rounds the plain reference
follows; the window then continues the same trainer from round 3.

Traffic-file keys: workers, samples_per_worker, epochs, fog_cells,
alpha (Dirichlet label skew), batch_size, lr, momentum, limits."""
from __future__ import annotations

import time

import numpy as np

from chipbench import common, fldata, flops
from chipbench.reference import cnn_fl

#: the rounds set-up runs and the reference follows
CHECKED_ROUNDS = 3
#: workers whose local training is compared with the reference's
SAMPLED_WORKERS = 16
#: distinct key sets the window cycles through
KEY_ROUNDS = 16
#: images the loss is evaluated on
EVAL = 2048


class Cohort:
    """The program's trainer and exchange, with the cell's inputs."""

    def __init__(self, cfgfile: dict, t: dict, seed: int):
        import jax
        import jax.numpy as jnp
        from repro.configs import get_config
        from repro.core import federated, hierarchy
        from repro.core.client import LocalTrainer
        from repro.models import build_model
        self.c, self.t = cfgfile, t
        self.W, S = t["workers"], t["samples_per_worker"]
        model = build_model(get_config(cfgfile["program_arch"]))
        key = common.seed_key(seed)
        self.g0 = fldata.cnn_weights(
            cfgfile, jax.random.fold_in(key, 0),
            jax.eval_shape(model.init, jax.random.key(0)))
        self.labels = jnp.asarray(fldata.labels(
            self.W, S, t["alpha"], cfgfile["num_classes"], seed))
        self.images = fldata.images(jax.random.fold_in(key, 1),
                                    self.labels, fldata.prototypes(cfgfile))
        keys = jax.random.split(jax.random.fold_in(key, 2),
                                KEY_ROUNDS * self.W)
        self.keys = [keys[r * self.W:(r + 1) * self.W]
                     for r in range(KEY_ROUNDS)]
        self.trainer = LocalTrainer(model, lr=t["lr"], momentum=t["momentum"],
                                    batch_size=t["batch_size"])
        self.weights = np.full(self.W, float(S))
        self.cell_of = np.arange(self.W) % t["fog_cells"]
        self.federated, self.hierarchy = federated, hierarchy
        bs = min(t["batch_size"], S)
        self.samples_per_round = self.W * (S // bs) * bs * t["epochs"]
        jax.block_until_ready((self.g0, self.images, self.keys))

    def round(self, params, r: int, spans):
        """One round from global `params`; returns (new params, stacked
        local params)."""
        import jax
        with spans("train"):
            stacked = self.trainer.train_cohort(
                params, self.images, self.labels,
                self.keys[r % KEY_ROUNDS], epochs=self.t["epochs"])
        with spans("exchange"):
            folded = self.hierarchy.hierarchical_sync_aggregate(
                stacked, self.weights, self.cell_of, compress="q8",
                base_params=self.federated.stack_islands(params, self.W))
            new = self.federated.island_slice(folded, 0)
        with spans("sync"):
            jax.block_until_ready(new)
        return new, stacked

    def reference(self, rounds: int, dtype, half_batch=False):
        """The plain reference's global params after rounds 1..n and its
        round-1 local params (all workers)."""
        import jax.numpy as jnp
        t = self.t
        g, hist, first = self.g0, [], None
        for r in range(rounds):
            g, local = cnn_fl.round_(
                g, self.images, self.labels, self.keys[r],
                jnp.asarray(self.cell_of), epochs=t["epochs"],
                batch=t["batch_size"], lr=t["lr"], momentum=t["momentum"],
                n_conv=len(self.c["conv_channels"]),
                n_cells=t["fog_cells"], dtype=dtype, half_batch=half_batch)
            hist.append(g)
            first = local if first is None else first
        return hist, first

    def loss(self, p):
        x = self.images.reshape((-1,) + self.images.shape[2:])[:EVAL]
        y = self.labels.reshape(-1)[:EVAL]
        return float(cnn_fl.eval_loss(p, x, y,
                                      n_conv=len(self.c["conv_channels"])))


def leaf_gaps(got: dict, want: dict, base: dict) -> list[float]:
    """Each leaf's gap between the norms of got - base and want - base,
    over the larger of that leaf's reference norm and the median leaf's.
    Leaves the reference leaves unmoved (under a thousandth of the median)
    are left out: only round-off moves them."""
    def norms(tree):
        return {k: float(np.linalg.norm(np.asarray(tree[k], np.float64)
                                        - np.asarray(base[k], np.float64)))
                for k in base}
    a, b = norms(got), norms(want)
    med = float(np.median(list(b.values())))
    return [abs(a[k] - b[k]) / max(b[k], med) for k in b
            if b[k] >= 1e-3 * med]


def numbers(cohort: Cohort, prog_hist, prog_local, ref_hist, ref_local,
            sampled) -> dict:
    """The compared numbers of one run (see PERF.md).  `prog_local`
    holds the sampled workers' round-1 params, `ref_local` every
    worker's."""
    import jax
    g0 = jax.device_get(cohort.g0)
    pick = lambda tree, i: {k: np.asarray(v[i]) for k, v in tree.items()}
    prog_local = jax.device_get(prog_local)
    ref_local = jax.device_get({k: v[np.asarray(sampled)]
                                for k, v in ref_local.items()})
    local = max(max(leaf_gaps(pick(prog_local, j), pick(ref_local, j), g0))
                for j in range(len(sampled)))
    ph = [jax.device_get(p) for p in prog_hist]
    rh = [jax.device_get(p) for p in ref_hist]
    loss = max(abs(a / b - 1.0) for a, b in zip(
        map(cohort.loss, ph), map(cohort.loss, rh)))
    update, change = leaf_gaps(ph[0], rh[0], g0), leaf_gaps(ph[-1], rh[-1], g0)
    return {"local_gap": local, "update_gap": max(update),
            "change_gap": max(change),
            "update_gap_median": float(np.median(update)),
            "change_gap_median": float(np.median(change)), "loss_gap": loss}


def judge(got: dict, limits: dict):
    """(correct, {name: (value, limit)}) of one run's compared numbers."""
    checks = {k: (got[k], limits[k]) for k in limits}
    return all(v <= lim for v, lim in checks.values()), checks


def first_rounds(cohort: Cohort, seed: int, spans):
    """Rounds 1..CHECKED_ROUNDS through the program: (sampled workers,
    global params after each round, the sampled workers' round-1 local
    params)."""
    import jax.numpy as jnp
    sampled = np.sort(np.random.default_rng([seed, 5]).choice(
        cohort.W, min(SAMPLED_WORKERS, cohort.W), replace=False))
    params, hist, local = cohort.g0, [], None
    for r in range(CHECKED_ROUNDS):
        params, stacked = cohort.round(params, r, spans)
        hist.append(params)
        if local is None:
            idx = jnp.asarray(sampled)
            local = {k: v[idx] for k, v in stacked.items()}
    return sampled, hist, local


def run(cfgfile, t, *, seed, seconds, traced, clock, t_start, devs):
    import jax.numpy as jnp
    from chipbench import tracing
    cohort = Cohort(cfgfile, t, seed)
    sampled, hist, local = first_rounds(cohort, seed, common.Spans(traced))
    params = hist[-1]
    setup_s = time.perf_counter() - t_start
    compiles = clock.count
    common.say(setup_s=setup_s, compile_s=clock.seconds,
               compiles_in_setup=compiles)

    spans = common.Spans(traced)
    trace = tracing.Capture(traced)
    done, t_last, r = 0, 0.0, CHECKED_ROUNDS
    with trace:
        t0 = time.perf_counter()
        t_end = t0 + seconds
        with spans("window"):
            while time.perf_counter() < t_end:
                params, _ = cohort.round(params, r, spans)
                r += 1
                now = time.perf_counter()
                if now <= t_end:
                    done, t_last = done + 1, now
    rounds_run = r - CHECKED_ROUNDS
    device = common.device_info(devs)
    common.say(rounds_completed=done, rounds_run=rounds_run,
               compiles_in_window=clock.count - compiles,
               peak_bytes=device["memory_peak_bytes"],
               bytes_limit=common.bytes_limit(devs))
    rate = done * cohort.samples_per_round / max(t_last - t0, 1e-9)
    e2e = {"setup_s": setup_s, "fl_samples_per_s": rate if done else None}

    readings = {"trace": trace.reduce(),
                "counters": {"rounds": rounds_run},
                "quant8_bytes_per_round": quant8_bytes(cohort),
                "flops": rounds_run * cohort.samples_per_round * 3
                * flops.cnn_forward(cfgfile)}
    del params
    ref_hist, ref_local = cohort.reference(CHECKED_ROUNDS, jnp.float32)
    correct, checks = judge(numbers(cohort, hist, local, ref_hist,
                                    ref_local, sampled), t["limits"])
    if traced:
        device.update(trace.device_fields())
    return {"e2e": e2e, "readings": readings, "device": device,
            "correct": correct, "attempted": rounds_run + CHECKED_ROUNDS,
            "failed": 0, "checks": checks,
            "breakdown": trace.breakdown() if traced else None}


def quant8_bytes(cohort: Cohort) -> int:
    """Bytes the exchange's rowwise quantise needs per round: each of the
    two hops quantises every stacked (W, ...) delta leaf once."""
    shapes = flops.cnn_params(cohort.c)
    return 2 * sum(flops.quant8_bytes((cohort.W,) + s)
                   for s in shapes.values())
