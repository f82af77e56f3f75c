"""Device time of one paged decode step: the mean execution of the serve
loop's `_decode_impl` program in the traced window (ms)."""
from chipbench.metrics import program_seconds


def read(readings):
    hit = program_seconds(readings, "_decode_impl")
    return None if hit is None else 1e3 * hit[1] / hit[0]
