"""Share of the traced window, in %, in which no op ran on the chip
while a request was waiting between its enqueue and its batch's own
device start (the tracer's spans mapped onto the trace's clock by the
measured offset; open loop)."""
from benchmarks.chip import served


def read(run):
    return served.idle_with_work_pct(run)
