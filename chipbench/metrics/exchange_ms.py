"""Device time of the fog exchange per round: every program the round
runs other than the local training (`cohort_impl`), that is what the
eagerly called fold dispatches, its Pallas quantise included (ms)."""


def read(readings):
    trace = readings.get("trace")
    rounds = readings["counters"]["rounds"]
    if not trace or not rounds:
        return None
    rest = [s for k, (_, s) in trace["programs"].items()
            if "cohort_impl" not in k]
    return 1e3 * sum(rest) / rounds if rest else None
