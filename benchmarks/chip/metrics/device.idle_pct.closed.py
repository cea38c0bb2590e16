"""Share of the window in which no op ran on the chip, in % (closed
loop)."""
from benchmarks.chip import readers


def read(run):
    busy = readers.busy_s(run)
    return None if busy is None else 100.0 * (1 - busy / readers.window_s(run))
