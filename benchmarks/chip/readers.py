"""Arithmetic shared by the metric readers under `metrics/`.

A reader takes the finished `Run` (see run_cell.py) and returns a number,
or None where the run holds nothing to read, in which case the metric is
left out of the result line.
"""
from __future__ import annotations

import numpy as np


def latencies_ms(run):
    """Latency of every request due in the window, from its due time;
    a request never answered counts the time it was waited for, so it
    misses every latency limit."""
    return np.array([((r["done"] if "result" in r else run.gave_up)
                      - r["due"]) * 1e3 for r in run.records])


def percentile(values, q: float):
    return float(np.percentile(values, q)) if len(values) else None


def answered_in_window(run) -> int:
    return sum(1 for r in run.records
               if "result" in r and r["done"] <= run.t1)


def window_spans(run):
    """The search spans that started in the measured window."""
    return [s for s in run.spans if run.t0 <= s[0] < run.t1]


def search_ms_per_query(run):
    spans = window_spans(run)
    real = sum(s[2] for s in spans)
    return sum(s[1] - s[0] for s in spans) * 1e3 / real if real else None


def roofline_pct(run, kernel: str):
    """The kernel's least time over its device time, in %, over every
    search in the trace. The least time of a search is the larger of its
    operations over one chip's highest operation rate and its bytes over
    its HBM bandwidth; the counts are of the cell's whole work, and the
    device time is summed over the cell's chips, so work split over
    chips reads at most 100%."""
    if not run.traces:
        return None
    mod = run.catalog.module("kernels", kernel)
    ns = sum(t.kernel_ns(mod.PATTERN) for t in run.traces)
    if ns <= 0:
        return None
    peak_ops = max(run.peaks["ops_per_s"].values())
    least = 0.0
    for s in run.traced_spans:
        ops, nbytes = mod.search_counts(run.config, run.pages, s[2])
        least += max(ops / peak_ops, nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ns / 1e9)


def busy_s(run):
    """Seconds in the measured window in which an op ran on the first
    chip."""
    if run.trace is None:
        return None
    lo, hi = run.trace.window()
    return run.trace.busy(lo, hi)[0] / 1e9


def busy_s_per_chip(run):
    """`busy_s` of each of the cell's chips, in mesh order."""
    lo, hi = run.trace.window()
    return [t.busy(lo, hi)[0] / 1e9 for t in run.traces]


def window_s(run):
    lo, hi = run.trace.window()
    return (hi - lo) / 1e9
