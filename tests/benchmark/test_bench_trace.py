"""Reduction of a profiler trace to busy time, idle gaps and kernel
time: on a hand-made trace with known answers, and on a small trace
recorded on a TPU v5e (fixtures/)."""
from __future__ import annotations

import os

import pytest

from benchmarks.chip.trace import Trace, op_name, top_ops

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures")


def hand_trace():
    ms = 10**6
    return Trace(
        ops=[("quantized_maxsim_pallas.1", 1 * ms, 4 * ms),
             ("quantized_maxsim_pallas.2", 3 * ms, 5 * ms),   # overlaps
             ("while.3", 6500000, 8500000),                    # encloses
             ("copy.4", 7 * ms, 8 * ms),
             ("quantized_maxsim_pallas.1", 12 * ms, 14 * ms)],
        spans=[("bench.window", 0, 13 * ms),
               ("bench.search", 5500000, 9 * ms)])


def test_busy_is_the_union_of_ops_in_the_window():
    tr = hand_trace()
    lo, hi = tr.window()
    busy, merged = tr.busy(lo, hi)
    # [1, 5] + [6.5, 8.5] + [12, 13] (clipped at the window's end)
    assert busy == 7 * 10**6
    assert merged == [[1e6, 5e6], [6.5e6, 8.5e6], [12e6, 13e6]]


def test_kernel_time_and_top_ops():
    tr = hand_trace()
    lo, hi = tr.window()
    assert tr.kernel_ns("quantized_maxsim_pallas", lo, hi) == 6 * 10**6
    assert tr.kernel_ns("hamming_maxsim_pallas", lo, hi) == 0
    # the while's self time excludes the copy it encloses
    assert top_ops([tr], lo, hi) == [["quantized_maxsim_pallas", 0.006],
                                     ["while", 0.001], ["copy", 0.001]]


def test_op_names_from_hlo_text():
    assert op_name("%quantized_maxsim_pallas.6 = f32[1,1,256]{2,1,0} "
                   "custom-call(bf16[1,96,256] %x)") == (
        "quantized_maxsim_pallas.6")
    assert op_name("jit_search(123)") == "jit_search(123)"


def test_idle_gaps_named_by_host_span():
    tr = hand_trace()
    lo, hi = tr.window()
    # gaps: [0,1] window, [5,6.5] search at 5.75, [8.5,12] window
    assert tr.idle_gaps(lo, hi) == [["bench.window", 0.0035],
                                    ["bench.search", 0.0015],
                                    ["bench.window", 0.001]]


def test_window_must_be_marked_once():
    with pytest.raises(ValueError):
        Trace().window()


def test_recorded_chip_trace():
    """One B=1 search over 2^18 pages (colpali-hpc) on a TPU v5e, with
    5 ms of idle on either side: 19,487 ops, the scan's 1,024 kernel
    calls and the rerank's one (`vmap_jit_quantized_maxsim_pallas__`)."""
    import numpy as np

    tr = Trace.from_json(os.path.join(FIXTURE, "v5e_adc_search.json.gz"))
    lo, hi = tr.window()
    assert len(tr.ops) == 19487
    mask = np.zeros(hi - lo, bool)            # the union, done by brute force
    for _, s, e in tr.ops:
        mask[s - lo:e - lo] = True
    busy = tr.busy(lo, hi)[0]
    assert busy == int(mask.sum()) == 124_523_337
    assert tr.kernel_ns("quantized_maxsim_pallas", lo, hi) == 115_270_042
    assert sum(1 for o in tr.ops
               if "quantized_maxsim_pallas" in o[0]) == 1025
    assert top_ops([tr], lo, hi, 2) == [["quantized_maxsim_pallas",
                                         0.115120775],
                                        ["sort", 0.003910897]]
    gaps = tr.idle_gaps(lo, hi, 2)
    assert [g[0] for g in gaps] == ["bench.window", "bench.window"]
    assert sum(g[1] for g in gaps) == pytest.approx(
        (hi - lo - busy) / 1e9, abs=1e-6)
