"""Device self time of the ops under program scope `kernel.layout` (the
code kernels' casts and transposes of each block's codes and mask, docs
on lanes), over device busy time in the traced window, in % (open
loop)."""
from benchmarks.chip import served


def read(run):
    return served.stage_pct(run, "kernel.layout")
