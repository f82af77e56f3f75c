"""Run one benchmark cell once, on the chips of the machine it starts on.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

The cell, its configuration and its traffic come from BENCHMARK.json and
the files it names; the traffic file's `driver` names the module under
chipbench/drivers/ that runs it.  With --trace 0 the result holds the
cell's end-to-end metrics; with --trace 1 the same run is traced and the
result holds its per-layer metrics, each read by chipbench/metrics/<name>.py.

Off a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result.  Earlier lines of stdout carry counters; the last line
is one JSON object: correct, attempted, failed, metrics, device (and
breakdown when traced), and last `checks`, each number compared with its
limit.  The same checks close stderr.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                              # noqa: E402
import importlib                                             # noqa: E402
import importlib.util                                        # noqa: E402
import json                                                  # noqa: E402
import sys                                                   # noqa: E402
from pathlib import Path                                     # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chipbench import common                                 # noqa: E402


def cell_files(name: str):
    """(cell, configuration file, traffic file, BENCHMARK.json)."""
    bench = common.load_json(common.ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload '{name}'; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfgfile = common.load_json(common.ROOT / entry["file"])
    traffic = common.load_json(common.BENCH_DIR / "traffic"
                               / f"{cell['traffic']}.json")
    return cell, cfgfile, traffic, bench


def applies(metric: dict, cell: str, reported: set) -> bool:
    """A metric with `workloads` is the listed cells'; one without is every
    cell's that reports the end-to-end metric it moves (or every cell's)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def read_metric(name: str, readings: dict):
    """The per-layer metric `name`, by its reader; None when it finds
    nothing to read."""
    path = common.BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(readings)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell, cfgfile, traffic, bench = cell_files(args.workload)
    common.program_path()
    try:
        devs = common.require_chip(cell["chips"])
    except common.NoChip as e:
        print(f"chipbench: {e}; nothing was run", file=sys.stderr)
        return 3
    common.compile_cache()
    clock = common.CompileClock()
    driver = importlib.import_module(f"chipbench.drivers.{traffic['driver']}")
    res = driver.run(cfgfile, traffic, seed=args.seed,
                     seconds=args.seconds, traced=bool(args.trace),
                     clock=clock, t_start=T_START, devs=devs)
    e2e = [m for m in bench["end_to_end"] if applies(m, cell["name"], set())]
    reported = {m["name"] for m in e2e}
    if args.trace:
        chosen = [m for m in bench["per_layer"]
                  if applies(m, cell["name"], reported)]
        values = {m["name"]: read_metric(m["name"], res["readings"])
                  for m in chosen}
    else:
        chosen = e2e
        values = {m["name"]: res["e2e"][m["name"]] for m in chosen}
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in chosen if values[m["name"]] is not None}
    device = res["device"]
    out = {"correct": bool(res["correct"]), "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if args.trace and res.get("breakdown"):
        out["breakdown"] = res["breakdown"]
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in res["checks"].items()}
    for k, (v, lim) in res["checks"].items():
        print(f"check {k}: {v} (limit {lim})", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
