"""Readers of the per-layer metrics: one module per metric, named as the
metric, each with `read(readings) -> float | None`.

`readings` is what a driver hands over after the window: "trace" (the
reduction of chipbench/tracing.py, or None), "spans", counters of the
driver's own, and the work done in the window counted from shapes.  A
reader that finds nothing to read returns None and the metric is left out
of the result line."""
from __future__ import annotations


def program_seconds(readings, *names):
    """(executions, device seconds) of the programs whose name holds any
    of `names`, in the traced window; None without a trace or a match."""
    trace = readings.get("trace")
    if not trace:
        return None
    hits = [v for k, v in trace["programs"].items()
            if any(n in k for n in names)]
    if not hits:
        return None
    return sum(c for c, _ in hits), sum(s for _, s in hits)


def idle_share(readings):
    """Share of the traced window in which no operation ran (%)."""
    trace = readings.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def mfu(readings):
    """Operations the work needs, over the window, over the chip's bf16
    peak (%)."""
    trace = readings.get("trace")
    if not trace or not readings.get("flops"):
        return None
    from chipbench import peaks
    peak = peaks.of(trace["device_kind"])["bf16_flops"]
    return 100.0 * readings["flops"] / trace["window_s"] / peak
