"""Median latency of every request due in the window, from its due
time to its answer (open loop)."""
from benchmarks.chip import readers


def read(run):
    return readers.percentile(readers.latencies_ms(run), 50)
