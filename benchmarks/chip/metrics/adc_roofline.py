"""Share of the ADC kernel's roofline: its least time on this chip
(kernels/adc.py counts against peaks.json) over its device time in the
trace, in %. Reads `adc_roofline.open` and `adc_roofline.closed`."""
from benchmarks.chip import readers


def read(run):
    return readers.roofline_pct(run, "adc")
