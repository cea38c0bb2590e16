"""95th percentile latency of every request due in the window, from
its due time to its answer (open loop): all requests, not a median of
chunks."""
from benchmarks.chip import readers


def read(run):
    return readers.percentile(readers.latencies_ms(run), 95)
