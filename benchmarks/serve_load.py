"""Serving load benchmark -> BENCH_serve.json (the perf trajectory for
the paged serving path; run by the `serve` CI step).

Drives the block-table paged serve loop (and the contiguous baseline)
with the seeded open-loop generator (launch/loadgen.py) at a smoke-scale
target QPS on the granite smoke model, and reports p50/p99 request
latency, time-to-first-token, and output tokens/s.  A shared-prefix
workload exercises prefix sharing; a parity pass replays the same trace
through both cache disciplines on a virtual clock and requires
token-identical outputs.

  PYTHONPATH=src python benchmarks/serve_load.py          # measure + write
  PYTHONPATH=src python benchmarks/serve_load.py --check  # compare-or-commit:
      writes BENCH_serve.json if missing, else fails (exit 1) when any cell
      regressed below REGRESSION_FACTOR x its committed tokens/s or above
      REGRESSION_FACTOR x its committed p99.  Hard invariants (paged ==
      contiguous token streams, p99 bound, tokens/s floor, prefix sharing
      active) are enforced on EVERY run, check or not.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402

from repro.configs import get_smoke_config  # noqa: E402
from repro.launch import loadgen  # noqa: E402
from repro.launch.serve_loop import PagedServeLoop, ServeLoop  # noqa: E402
from repro.models import build_model  # noqa: E402

BENCH_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_serve.json")
REGRESSION_FACTOR = 3.0   # fail --check when > 3x off the committed cell

ARCH = "granite-20b"
QPS = 12.0
DURATION_S = 3.0
# hard invariants, enforced every run (generous: CI boxes are slow)
P99_BOUND_MS = 20_000.0
TOKENS_PER_S_FLOOR = 5.0

POOL = dict(max_batch=4, num_blocks=48, block_size=8, chunk=32)


def _loops(model, params):
    paged = PagedServeLoop(model, params, **POOL)
    contiguous = ServeLoop(model, params, max_batch=POOL["max_batch"],
                           max_len=POOL["num_blocks"] * POOL["block_size"])
    return paged, contiguous


def _load_cfg(vocab, shared=False):
    return loadgen.LoadConfig(
        qps=QPS, duration_s=DURATION_S, seed=7, vocab_size=vocab,
        prompt_mean=20, prompt_max=80, out_mean=8, out_max=24,
        shared_prefix_frac=0.5 if shared else 0.0, shared_prefix_len=16)


def measure(model, params) -> tuple[dict, dict]:
    vocab = model.cfg.vocab_size
    cells = {}

    # warm the jit caches (prefill buckets + decode) outside timed regions
    warm = loadgen.LoadConfig(qps=50, duration_s=0.2, seed=1,
                              vocab_size=vocab, prompt_mean=20,
                              prompt_max=80)
    for loop in _loops(model, params):
        loadgen.run_trace(loop, loadgen.generate(warm), tick_s=None)

    for name, shared, paged in (("paged_smoke", False, True),
                                ("paged_shared_prefix", True, True),
                                ("contiguous_smoke", False, False)):
        trace = loadgen.generate(_load_cfg(vocab, shared))
        ploop, cloop = _loops(model, params)
        loop = ploop if paged else cloop
        t0 = time.monotonic()
        records = loadgen.run_trace(loop, trace, tick_s=None)
        wall = time.monotonic() - t0
        cell = loadgen.summarize(records, wall)
        cell["qps"] = QPS
        if paged:
            cell["preemptions"] = loop.counters["preemptions"]
            cell["shared_blocks"] = loop.alloc.stats["shared_blocks"]
            cell["evictions"] = loop.alloc.stats["evictions"]
        cells[name] = cell
        print(f"[serve_load] {name}: p50 {cell['p50_ms']}ms "
              f"p99 {cell['p99_ms']}ms  {cell['tokens_per_s']} tok/s "
              f"({cell['n_requests']} reqs)", flush=True)

    # parity: identical virtual-clock trace through both disciplines
    trace = loadgen.generate(_load_cfg(vocab, shared=True))
    ploop, cloop = _loops(model, params)
    got = loadgen.run_trace(ploop, trace, tick_s=0.01)
    want = loadgen.run_trace(cloop, trace, tick_s=0.01)
    mismatches = sum(g.out != w.out for g, w in zip(got, want))
    parity = {"n_requests": len(trace), "mismatches": mismatches,
              "shared_blocks": ploop.alloc.stats["shared_blocks"]}
    print(f"[serve_load] parity: {mismatches}/{len(trace)} mismatched "
          f"({parity['shared_blocks']} prefix blocks shared)", flush=True)
    return cells, parity


def check_invariants(cells: dict, parity: dict) -> list[str]:
    bad = []
    if parity["mismatches"]:
        bad.append(f"paged/contiguous token streams diverge: "
                   f"{parity['mismatches']}/{parity['n_requests']}")
    if parity["shared_blocks"] == 0:
        bad.append("shared-prefix workload shared no blocks")
    for name in ("paged_smoke", "paged_shared_prefix"):
        c = cells[name]
        if c["p99_ms"] > P99_BOUND_MS:
            bad.append(f"{name}: p99 {c['p99_ms']}ms > {P99_BOUND_MS}ms")
        if c["tokens_per_s"] < TOKENS_PER_S_FLOOR:
            bad.append(f"{name}: {c['tokens_per_s']} tok/s < "
                       f"{TOKENS_PER_S_FLOOR}")
    return bad


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="compare against committed BENCH_serve.json "
                         "(write it when missing)")
    ap.add_argument("--out", default=BENCH_PATH)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(ARCH)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    cells, parity = measure(model, params)

    bad = check_invariants(cells, parity)
    if bad:
        print(f"[serve_load] FAIL invariants: {bad}")
        return 1

    result = {
        "bench": "serve_load",
        "arch": f"{ARCH}-smoke",
        "workload": f"open-loop poisson {QPS} qps x {DURATION_S}s, "
                    "lognormal prompts / geometric outputs",
        "pool": POOL,
        "cells": cells,
        "parity": parity,
    }

    if args.check and os.path.exists(args.out):
        with open(args.out) as f:
            committed = json.load(f)
        failures = []
        for name, cell in cells.items():
            old = committed.get("cells", {}).get(name)
            if old is None:
                continue
            tps_floor = old["tokens_per_s"] / REGRESSION_FACTOR
            p99_ceil = old["p99_ms"] * REGRESSION_FACTOR
            ok = (cell["tokens_per_s"] >= tps_floor
                  and cell["p99_ms"] <= p99_ceil)
            print(f"[serve_load] check {name}: {cell['tokens_per_s']} tok/s "
                  f"(floor {tps_floor:.2f}), p99 {cell['p99_ms']}ms "
                  f"(ceil {p99_ceil:.0f}) {'OK' if ok else 'REGRESSED'}")
            if not ok:
                failures.append(name)
        if failures:
            print(f"[serve_load] FAIL: serving regression in {failures}")
            return 1
        print("[serve_load] check passed")
        return 0

    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"[serve_load] wrote {os.path.abspath(args.out)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
