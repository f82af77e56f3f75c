"""The program's own spans and kernel names as the reduction sees them,
and the reader of `quant8_kernel_roofline`.

The program names its host spans `flight.<layer>.<phase>` (src/repro/
trace.py) and its quantise kernel `quant8_rowwise`.  `tracing.reduce`
credits an idle gap to the innermost host span of the extract, whoever
wrote it, so a program span inside a harness span takes its gaps."""
import importlib.util
import json
from pathlib import Path

import pytest

from chipbench import peaks, tracing

DATA = Path(__file__).parent / "data"
METRICS = Path(__file__).parents[1] / "metrics"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def tick_extract():
    """One chip, window [0, 1000] ns: a harness tick over all of it, and
    inside it the program's tick span with an admission (its prefill
    inside), the decode step and the readback."""
    return {"devices": {"/device:TPU:0": {
        "ops": [[50, 200, "fusion"], [300, 600, "convert"],
                [800, 900, "fusion"]],
        "programs": [[50, 200, "jit__chunk_impl"],
                     [300, 600, "jit__decode_impl"],
                     [800, 900, "jit__decode_impl"]]}},
        "host": [[0, 1000, "window"], [0, 1000, "tick"],
                 [30, 990, "flight.serve.tick"],
                 [40, 280, "flight.serve.admit"],
                 [45, 210, "flight.serve.prefill"],
                 [290, 310, "flight.serve.step"],
                 [610, 790, "flight.serve.readback"]]}


def test_program_spans_take_the_gaps_of_the_harness_span():
    """Each gap goes to the innermost span of either kind around its
    midpoint: [0, 50] lies before the program's tick, [200, 300] (mid 250)
    after the prefill but inside its admission, [600, 800] in the
    readback and [900, 1000] (mid 950) in the tick alone."""
    red = tracing.reduce(tick_extract(), "TPU v5 lite")
    ns = 1e-9
    assert red["gaps"] == {"tick": pytest.approx(50 * ns),
                           "flight.serve.admit": pytest.approx(100 * ns),
                           "flight.serve.readback": pytest.approx(200 * ns),
                           "flight.serve.tick": pytest.approx(100 * ns)}
    assert sum(red["gaps"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])


def test_program_spans_leave_busy_programs_and_ops_alone():
    ex = tick_extract()
    harness_only = {**ex, "host": [h for h in ex["host"]
                                   if not h[2].startswith("flight.")]}
    a = tracing.reduce(ex, "TPU v5 lite")
    b = tracing.reduce(harness_only, "TPU v5 lite")
    for key in ("busy_s", "window_s", "programs", "ops"):
        assert a[key] == b[key], key
    assert b["gaps"] == {"tick": pytest.approx(450e-9)}


def test_recorded_fl_extract_reduces_as_before():
    """The committed fl-cohort256 recording, pinned: busy, window and
    programs exactly, ops by count and total."""
    rec = json.loads((DATA / "fl-cohort256.json").read_text())
    red = tracing.reduce(rec["extract"], "TPU v5 lite")
    assert red["busy_s"] == pytest.approx(2.0254990410000002, rel=1e-12)
    assert red["window_s"] == pytest.approx(2.073494939, rel=1e-12)
    want = {"jit_add": (24, 0.0011202890000000004),
            "jit_broadcast_in_dim": (32, 0.00020187000000000002),
            "jit_cohort_impl": (2, 2.0034629080000004),
            "jit_convert_element_type": (28, 0.000513229),
            "jit_dot_general": (24, 0.0007468280000000002),
            "jit_dynamic_slice": (12, 0.000196436),
            "jit_multiply": (24, 0.000789287),
            "jit_quantize_blocked": (24, 0.015804854),
            "jit_reshape": (36, 0.0015516160000000002),
            "jit_squeeze": (4, 3.7990000000000004e-06),
            "jit_subtract": (24, 0.001121115)}
    assert set(red["programs"]) == set(want)
    for k, (n, s) in want.items():
        assert red["programs"][k][0] == n
        assert red["programs"][k][1] == pytest.approx(s, rel=1e-12)
    assert len(red["ops"]) == 55
    assert sum(red["ops"].values()) == pytest.approx(2.0254990409999856,
                                                     rel=1e-12)
    assert red["ops"]["cohort_impl/select-and-scatter"] == pytest.approx(
        0.4445050690000001, rel=1e-12)


def fl_readings(ops, programs, rounds=2):
    return {"trace": {"device_kind": "TPU v5 lite", "busy_s": 1.0,
                      "window_s": 1.0, "ops": ops, "programs": programs,
                      "gaps": {}},
            "counters": {"rounds": rounds},
            "quant8_bytes_per_round": 819_000}


def test_quant8_kernel_roofline_reads_the_named_kernel():
    read = reader("quant8_kernel_roofline")
    # 2 rounds x 819 kB at 819 GB/s need 2 us; the kernel ran 4 us in the
    # wrapper program and 4 us inside another, beside 8 us of copies
    r = fl_readings({"quantize_blocked/quant8_rowwise": 4e-6,
                     "exchange/quant8_rowwise": 4e-6,
                     "quantize_blocked/copy": 8e-6},
                    {"jit_quantize_blocked": [4, 16e-6]})
    assert read(r) == pytest.approx(25.0)
    # the program-level share counts the same bytes over the wrapper's time
    assert reader("quant8_roofline")(r) == pytest.approx(12.5)
    bw = peaks.of("TPU v5 lite")["hbm_bytes_per_s"]
    assert read(r) == pytest.approx(100 * 2 * 819_000 / bw / 8e-6)


@pytest.mark.parametrize("ops, rounds, trace", [
    ({"quantize_blocked/quantize_blocked": 4e-6}, 2, True),  # no name
    ({"exchange/quant8_rowwise": 4e-6}, 0, True),            # no round
    ({"exchange/quant8_rowwise": 4e-6}, 2, False),           # untraced
    ({"exchange/quant8_rowwise_x": 4e-6}, 2, True),          # other kernel
])
def test_quant8_kernel_roofline_reads_nothing(ops, rounds, trace):
    r = fl_readings(ops, {}, rounds)
    if not trace:
        r["trace"] = None
    assert reader("quant8_kernel_roofline")(r) is None


def recorded(name):
    rec = json.loads((DATA / name).read_text())
    return rec, tracing.reduce(rec["extract"], "TPU v5 lite")


def test_recorded_decode_tick_idles_in_program_spans():
    """A v5e decode tick: every idle gap lies in a `flight.serve.*` span,
    most of it in the per-slot readback."""
    _, red = recorded("serve-decode-spans.json")
    idle = red["window_s"] - red["busy_s"]
    ours = {k: v for k, v in red["gaps"].items()
            if k.startswith("flight.serve.")}
    assert sum(ours.values()) == pytest.approx(idle, rel=1e-6)
    assert max(ours, key=ours.get) == "flight.serve.readback"


def test_recorded_decode_ops_carry_scopes():
    """The recording keeps one scope path per op; the decode step's
    attention (its gather inside) outweighs every other named part."""
    rec, _ = recorded("serve-decode-spans.json")
    dev = next(iter(rec["extract"]["devices"].values()))
    assert len(dev["scopes"]) == len(dev["ops"])
    part = {}
    for (s, e, _), scope in zip(dev["ops"], dev["scopes"]):
        top = scope.split("/")[0] if scope else "-"
        part[top] = part.get(top, 0) + (e - s)
    assert {"embed", "attention", "mlp", "head"} <= set(part)
    assert "attention/paged_gather" in dev["scopes"]
    assert part["attention"] == max(v for k, v in part.items() if k != "-")


def test_recorded_round_exchange_idles_in_program_spans():
    _, red = recorded("fl-cohort256-spans.json")
    harness = sum(v for k, v in red["gaps"].items()
                  if not k.startswith("flight."))
    fl = sum(v for k, v in red["gaps"].items() if k.startswith("flight.fl."))
    assert fl > 0.99 * (fl + harness)
    assert {"flight.fl.edge_hop", "flight.fl.cloud_hop"} <= set(red["gaps"])


def test_quant8_kernel_roofline_on_a_recorded_round():
    """Two v5e rounds: the kernel's own share reads above the wrapper
    program's, over the same bytes."""
    from chipbench import common, flops
    _, red = recorded("fl-cohort256-spans.json")
    cfg = common.load_json(common.ROOT / "chipbench/configs/"
                           "flight-cnn-cifar.json")
    workers = common.load_json(common.BENCH_DIR / "traffic/cohort256.json")[
        "workers"]
    per_round = 2 * sum(flops.quant8_bytes((workers,) + s)
                        for s in flops.cnn_params(cfg).values())
    r = {"trace": red, "counters": {"rounds": 2},
         "quant8_bytes_per_round": per_round}
    kernel = reader("quant8_kernel_roofline")(r)
    program = reader("quant8_roofline")(r)
    assert 0 < program < kernel < 100
