"""Mean, over consecutive batches computed in the window, of the host
time from one batch's `serve.compute` end to the next one's start: D2H,
fan-out, the clients' turnaround, coalescing and staging (the server's
tracer records, closed loop)."""
from benchmarks.chip import served


def read(run):
    return served.gap_ms_per_batch(run)
