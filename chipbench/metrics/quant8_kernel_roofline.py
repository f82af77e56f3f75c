"""Roofline share of the Pallas int8 quantise kernel alone (%).

The least time is quant8_roofline's: the HBM bytes the quantise needs
(chipbench/flops.quant8_bytes, both hops of every traced round) over the
chip's HBM bandwidth.  The time is the device self time of the
operations named by the kernel, `quant8_rowwise` (its `pallas_call`
name), in whatever program runs them: the wrapper program's copies are
left out, and a program that takes the kernel in (a jitted exchange)
leaves the reading as it is.  A program whose kernel carries no name
reads nothing."""
from chipbench import peaks

KERNEL = "quant8_rowwise"


def read(readings):
    trace = readings.get("trace")
    rounds = readings["counters"]["rounds"]
    if not trace or not rounds:
        return None
    secs = sum(s for k, s in trace["ops"].items()
               if k.rsplit("/", 1)[-1] == KERNEL)
    if not secs:
        return None
    need = readings["quant8_bytes_per_round"] * rounds
    bw = peaks.of(trace["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * need / bw / secs
