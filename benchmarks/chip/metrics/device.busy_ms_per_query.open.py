"""Device busy time in the window (the union of op intervals in the
trace) per query answered in the window, in ms (open loop)."""
from benchmarks.chip import readers


def read(run):
    busy, n = readers.busy_s(run), readers.answered_in_window(run)
    return busy * 1e3 / n if busy is not None and n else None
