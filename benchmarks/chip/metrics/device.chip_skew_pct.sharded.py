"""How far the busiest and the idlest chip's busy time in the window lie
apart, over their mean, in %: a search waits for every chip, so the
slowest one sets its pace. None on one chip."""
from benchmarks.chip import readers


def read(run):
    if len(run.traces) < 2:
        return None
    busy = readers.busy_s_per_chip(run)
    mean = sum(busy) / len(busy)
    return 100.0 * (max(busy) - min(busy)) / mean if mean else None
