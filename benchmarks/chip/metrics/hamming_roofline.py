"""Share of the Hamming kernel's roofline: its least time on this chip
(kernels/hamming.py counts against peaks.json) over its device time in
the trace, in %."""
from benchmarks.chip import readers


def read(run):
    return readers.roofline_pct(run, "hamming")
