import json
from pathlib import Path

import pytest

from chipbench import tracing

DATA = Path(__file__).parent / "data"


def hand_made():
    """One chip, a 40 ns window: ops [0,10] [5,15] [20,30] busy 25 ns."""
    return {"devices": {"/device:TPU:0": {
        "ops": [[0, 10, "while"], [5, 9, "fusion"], [10, 15, "fusion"],
                [20, 30, "convolution"], [45, 50, "after"]],
        "programs": [[0, 15, "jit_cohort_impl"],
                     [20, 30, "jit_quantize_blocked"]]}},
        "host": [[0, 40, "window"], [12, 22, "exchange"],
                 [11, 25, "round"], [30, 41, "sync"]]}


def test_busy_programs_and_gaps_of_a_hand_made_trace():
    red = tracing.reduce(hand_made(), "TPU v5 lite")
    assert red["window_s"] == pytest.approx(40e-9)
    assert red["busy_s"] == pytest.approx(25e-9)
    assert red["programs"] == {"jit_cohort_impl": [1, pytest.approx(15e-9)],
                               "jit_quantize_blocked": [1,
                                                        pytest.approx(10e-9)]}
    # gap [15, 20] lies in the innermost span, "exchange"; [30, 40] in sync
    assert red["gaps"] == {"exchange": pytest.approx(5e-9),
                           "sync": pytest.approx(10e-9)}
    # self time: the while loop's 10 ns less the 4 ns of fusion inside it
    assert red["ops"] == {"cohort_impl/while": pytest.approx(6e-9),
                          "cohort_impl/fusion": pytest.approx(9e-9),
                          "quantize_blocked/convolution": pytest.approx(10e-9)}
    b = tracing.breakdown(red)
    assert b["idle_gaps"][0][0] == "sync"
    assert len(b["device_ops"]) == 3


def test_short_names():
    assert tracing.short("%fusion.137 = bf16[8,1]{1,0} fusion(%a), calls=x") \
        == "fusion"
    assert tracing.short("jit__decode_impl(1234)") == "jit__decode_impl"
    assert tracing.short("%select-and-scatter.5 = f32[2] select-and-scatter("
                         "%x)") == "select-and-scatter"


def test_no_device_or_no_window_reads_nothing():
    ex = hand_made()
    assert tracing.reduce({**ex, "devices": {}}, "TPU v5 lite") is None
    assert tracing.reduce({**ex, "host": []}, "TPU v5 lite") is None


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("*.json")))
def test_recorded_chip_trace(name):
    """Traces recorded on a v5e: busy within the window, the programs the
    per-layer metrics read are found, every gap attributed."""
    rec = json.loads((DATA / name).read_text())
    red = tracing.reduce(rec["extract"], "TPU v5 lite")
    assert 0 < red["busy_s"] <= red["window_s"]
    for program in rec["programs"]:
        assert any(program in k for k in red["programs"]), program
    idle = red["window_s"] - red["busy_s"]
    assert sum(red["gaps"].values()) == pytest.approx(idle, rel=1e-6)
    assert red["busy_s"] == pytest.approx(rec["busy_s"], rel=1e-9)
