"""Device self time of the ops under program scope `scan.merge` (the
scan engine's top-k merge, in the scan and in the rerank), over device
busy time in the traced window, in % (open loop)."""
from benchmarks.chip import served


def read(run):
    return served.stage_pct(run, "scan.merge")
