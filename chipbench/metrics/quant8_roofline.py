"""Roofline share of the Pallas int8 quantise in the exchange (%).

The kernel is bound by HBM: the least time is the bytes the work needs
(chipbench/flops.quant8_bytes: 4 B read and 1 B written per element, 4 B
per row scale, for every leaf of both hops of every traced round) over
the chip's HBM bandwidth; the share is that over the summed device time
of the kernel's program (`quantize_blocked`)."""
from chipbench import peaks
from chipbench.metrics import program_seconds


def read(readings):
    hit = program_seconds(readings, "quantize_blocked")
    rounds = readings["counters"]["rounds"]
    if hit is None or not rounds:
        return None
    need = readings["quant8_bytes_per_round"] * rounds
    bw = peaks.of(readings["trace"]["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * need / bw / hit[1]
