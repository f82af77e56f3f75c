"""The control readings of chipbench/control.py at a size the CPU holds:
the lower-precision reference put in the program's place must read worse
than the program, and fail a limit of the cell."""
from chipbench import common, control
from conftest import small_fl_traffic, small_serve_traffic


def test_serve_fp8_control_reads_wider_gaps(qwen_smoke):
    t = small_serve_traffic("chat-closed8")
    r = control.serve_readings(qwen_smoke, t, 2**31 + 17, 3.0)
    assert r["compared_tokens"] > 0 and r["wrong_lengths"] == 0
    assert r["control_fp8_logit_gap_top3"] > r["logit_gap_top3"]
    assert r["correct"]


def test_fl_controls_fail_a_limit():
    cfg = common.load_json(common.ROOT / "chipbench/configs/"
                           "flight-cnn-cifar.json")
    t = small_fl_traffic("cohort256")
    r = control.fl_readings(cfg, t, 2**31 + 19)
    assert r["correct"], r["program"]
    for name in ("control_bf16", "fault_half_batch",
                 "fault_state_unchanged"):
        assert not r[f"{name}_correct"], (name, r[name])
