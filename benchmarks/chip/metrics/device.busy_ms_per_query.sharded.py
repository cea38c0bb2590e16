"""Device busy time in the window per query answered in it, in ms, the
mean over the cell's chips: each chip scans its share of every query
(`device.chip_skew_pct.sharded` reads how far the chips differ)."""
from benchmarks.chip import readers


def read(run):
    if run.trace is None:
        return None
    busy, n = readers.busy_s_per_chip(run), readers.answered_in_window(run)
    return sum(busy) * 1e3 / len(busy) / n if n else None
