"""Readings that set a cell's limits: the program's compared numbers and
the lower-precision control's, seed by seed, in one process.

  python3 chipbench/control.py --workload <cell> --seeds 1 2 3 \
      [--seconds <window>]

Serve cells: each seed builds the server, runs one window of the cell's
traffic, and compares a sample of the finished requests with the float32
reference; the control is the same reference with every matmul input in
float8 e4m3, read as the reference's gap of the token the control puts
first (the mean of its three widest gaps, the widest and the mean).  FL
cells: the program's first rounds against the float32 reference; the
control is the reference in bfloat16, the faults the reference with half
of each minibatch left out and every worker returning the model it was
sent (a state left unchanged: 1 on the norm numbers by construction,
read here for the rest).  Each reading is judged by
the check the benchmark's own runs use (`serving.judge`,
`fl_sync.judge`): the program's must come out correct, every control's
not.  One JSON line per seed.

The benchmark's own runs never run this; chipbench/tests/test_control.py
runs it at a size the CPU holds."""
from __future__ import annotations

import argparse
import importlib
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chipbench import common                                 # noqa: E402


def serve_readings(cfgfile, t, seed: int, seconds: float) -> dict:
    from chipbench import serving
    mod = importlib.import_module(f"chipbench.drivers.{t['driver']}")
    server = serving.Server(cfgfile, t, seed, common.Spans(False))
    w = serving.Window(server, seconds, common.Spans(False))
    prime, drive = mod.drive_for(server, t, seed, seconds)
    prime(w)
    w.open()
    drive(w)
    w.close()
    done = w.done
    server.free_program()
    prog, ctrl, bad = serving.compare(server, done, seed, control=True)
    limit = t["limits"]["logit_gap_top3"]
    out = {"seed": seed, "finished": len(done),
           "compared_tokens": len(prog), "wrong_lengths": bad}
    for name, gaps in (("", prog), ("control_fp8_", ctrl)):
        out.update({f"{name}logit_gap_top3": serving.widest(gaps),
                    f"{name}logit_gap_max": float(gaps.max()),
                    f"{name}logit_gap_mean": float(gaps.mean())})
    out["correct"] = serving.judge(prog, bad, limit)[0]
    out["control_fp8_correct"] = serving.judge(ctrl, bad, limit)[0]
    return out


def fl_readings(cfgfile, t, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    from chipbench.drivers import fl_sync
    cohort = fl_sync.Cohort(cfgfile, t, seed)
    sampled, hist, local = fl_sync.first_rounds(cohort, seed,
                                                common.Spans(False))
    n = fl_sync.CHECKED_ROUNDS
    ref_hist, ref_local = cohort.reference(n, jnp.float32)
    out = {"seed": seed, "program": fl_sync.numbers(
        cohort, hist, local, ref_hist, ref_local, sampled)}
    out["correct"] = fl_sync.judge(out["program"], t["limits"])[0]
    readings = {}
    for name, kw in (("control_bf16", {"dtype": jnp.bfloat16}),
                     ("fault_half_batch", {"dtype": jnp.float32,
                                           "half_batch": True})):
        h, loc = cohort.reference(n, **kw)
        readings[name] = (h, {k: v[jnp.asarray(sampled)]
                              for k, v in loc.items()})
    # every worker returns the model it was sent
    readings["fault_state_unchanged"] = ([cohort.g0] * n, jax.tree.map(
        lambda g: jnp.broadcast_to(g, (len(sampled),) + g.shape),
        cohort.g0))
    for name, (h, loc) in readings.items():
        out[name] = fl_sync.numbers(cohort, h, loc, ref_hist, ref_local,
                                    sampled)
        out[f"{name}_correct"] = fl_sync.judge(out[name], t["limits"])[0]
    return out


def main(argv=None) -> int:
    from chipbench.run import cell_files
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    cell, cfgfile, t, bench = cell_files(args.workload)
    common.program_path()
    common.require_chip(cell["chips"])
    common.compile_cache()
    seconds = args.seconds or bench["run_seconds"]
    for seed in args.seeds:
        row = (fl_readings(cfgfile, t, seed) if t["driver"] == "fl_sync"
               else serve_readings(cfgfile, t, seed, seconds))
        print(json.dumps({"workload": cell["name"], **row}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
