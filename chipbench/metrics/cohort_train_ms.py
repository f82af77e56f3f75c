"""Device time of the vmapped local training per round: the
`cohort_impl` program's executions over the rounds traced (ms)."""
from chipbench.metrics import program_seconds


def read(readings):
    hit = program_seconds(readings, "cohort_impl")
    rounds = readings["counters"]["rounds"]
    return None if hit is None or not rounds else 1e3 * hit[1] / rounds
