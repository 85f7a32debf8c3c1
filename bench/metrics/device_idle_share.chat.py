"""Device: share of a steady slice of the window in which no operation
ran on the chip, in percent: 1 - (union of device-op intervals / slice),
from the profiler trace (bench/xplane.py)."""


def read(run):
    if not run.trace or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
