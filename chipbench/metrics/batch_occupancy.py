"""Mean live slots over max_batch across the window's decode ticks (%),
read by the harness from the loop after each tick."""


def read(readings):
    occ = readings.get("occupancy")
    if not occ:
        return None
    return 100.0 * sum(occ) / len(occ) / readings["max_batch"]
