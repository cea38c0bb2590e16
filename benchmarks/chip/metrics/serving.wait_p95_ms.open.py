"""95th percentile, over the requests answered in the window, of each
request's wait from its enqueue to its batch's own device start: the
start of its `serve.compute` span, or the end of the previous batch's,
whichever is later (the server's tracer records, open loop)."""
from benchmarks.chip import served


def read(run):
    return served.wait_p95_ms(run)
