"""The reading of the served path's own spans and device scopes beside
the profiler trace (`spantrace.py`, `served.py` and their metrics): on a
hand-made trace and run with known answers, on the recorded chip traces
(fixtures/), and on a tiny served run on the CPU."""
from __future__ import annotations

import os
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench_tiny import tiny_catalog
from benchmarks.chip import readers, run_cell, served, traced
from benchmarks.chip.catalog import Catalog
from benchmarks.chip.spantrace import (ScopedTrace, hlo_scopes,
                                      instruction, scope_of)
from benchmarks.chip.trace import Trace, top_ops
from repro.tracing import Span

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures")
CATALOG = Catalog()
MS = 10**6
OFF = 7_000      # the trace's clock runs 7 us ahead of the tracer's


def _span(name, a, b, sid, parent=None, **ids):
    return Span(name, int(a), int(b), sid, parent, ids)


def hand_run():
    """Three batches on the tracer's clock (ms): B1 computes [10, 30]
    for requests enqueued at 5 and 8; B2, staged while B1 ran, computes
    [25, 50] but has the device from 30, for a request enqueued at 20;
    B3 computes [60, 70] for one enqueued at 55. The device trace holds
    their ops, 7 us later, with program scopes."""
    recs = [
        _span("serve.stage", 8 * MS, 9.9 * MS, 11, 1, batch=1, rung=2),
        _span("serve.compute", 10 * MS, 30 * MS, 12, 1, batch=1, rung=2),
        _span("serve.d2h", 30 * MS, 30.5 * MS, 13, 1, batch=1),
        _span("serve.fanout", 30.5 * MS, 32 * MS, 14, 1, batch=1),
        _span("serve.request", 5 * MS, 32 * MS, 101, batch=1),
        _span("serve.request", 8 * MS, 32 * MS, 102, batch=1),
        _span("serve.batch", 5 * MS, 32.1 * MS, 1, batch=1, rung=2,
              requests=(101, 102)),
        _span("serve.stage", 21 * MS, 24 * MS, 21, 2, batch=2, rung=1),
        _span("serve.compute", 25 * MS, 50 * MS, 22, 2, batch=2, rung=1),
        _span("serve.d2h", 50 * MS, 51 * MS, 23, 2, batch=2),
        _span("serve.fanout", 51 * MS, 52 * MS, 24, 2, batch=2),
        _span("serve.request", 20 * MS, 52 * MS, 103, batch=2),
        _span("serve.batch", 20 * MS, 52.1 * MS, 2, batch=2, rung=1,
              requests=(103,)),
        _span("serve.coalesce", 55 * MS, 54 * MS + 57 * MS // 10, 30, 3,
              batch=3),
        _span("serve.stage", 54 * MS, 58 * MS, 31, 3, batch=3, rung=1),
        _span("serve.compute", 60 * MS, 70 * MS, 32, 3, batch=3, rung=1),
        _span("serve.request", 55 * MS, 72 * MS, 104, batch=3),
        _span("serve.batch", 55 * MS, 72.1 * MS, 3, batch=3, rung=1,
              requests=(104,)),
    ]
    host = [("bench.window", 0, 100 * MS, {})]
    jitter = iter([0, 3, -2, 5, 1, -4])
    for r in recs:
        if r.name in ("serve.stage", "serve.compute"):
            host.append((r.name, r.start_ns + OFF + next(jitter),
                         r.end_ns + OFF, dict(r.ids)))
    for r in recs:
        if r.name in ("serve.d2h", "serve.fanout"):
            host.append((r.name, r.start_ns + OFF, r.end_ns + OFF,
                         dict(r.ids)))
    host.sort(key=lambda s: s[1])
    ops = [  # (name, start, end, scope) on the trace's clock
        ("while.1", 10 * MS, 50 * MS, "search.scan"),
        ("quantized_maxsim_pallas_scan.2", 10 * MS, 30 * MS,
         "search.scan/scan.kernel"),
        ("convert_bitcast_fusion.3", 30 * MS, 32 * MS,
         "search.scan/scan.kernel/kernel.layout"),
        ("sort.4", 32 * MS, 40 * MS, "search.scan/scan.merge"),
        ("fusion.5", 40 * MS, 50 * MS, "search.rerank/scan.merge"),
        ("copy.6", 60 * MS, 70 * MS, ""),
    ]
    ops = [(n, s + OFF, e + OFF, sc) for n, s, e, sc in ops]
    trace = ScopedTrace([o[:3] for o in ops], [s[:3] for s in host],
                        [o[3] for o in ops], [s[3] for s in host])
    run = SimpleNamespace(serve_records=recs, t0=0.0, t1=0.1, trace=trace)
    offset, residuals = served.clock_offset(recs, trace)
    run.clock_offset_ns = offset
    return run, residuals


def test_clock_offset_is_recovered_from_matched_spans():
    run, residuals = hand_run()
    # the median of the jitters 0, 3, -2, 5, 1, -4 is 0.5: 0 ns in whole ns
    assert run.clock_offset_ns == OFF
    assert sorted(residuals) == [-4, -2, 0, 1, 3, 5]
    assert served.clock_offset(run.serve_records, ScopedTrace()) is None


def test_scope_self_time_and_attribution():
    run, _ = hand_run()
    tr = run.trace
    lo, hi = tr.window()
    # the while's self time is what its children leave: 40 - 40 = 0
    assert tr.scope_ns(lo, hi) == {
        "search.scan/scan.kernel": 20 * MS,
        "search.scan/scan.kernel/kernel.layout": 2 * MS,
        "search.scan/scan.merge": 8 * MS,
        "search.rerank/scan.merge": 10 * MS, "": 10 * MS}
    assert tr.stage_ns("scan.merge", lo, hi) == 18 * MS
    assert tr.stage_ns("scan.kernel", lo, hi) == 22 * MS
    assert tr.attributed_share(lo, hi) == pytest.approx(40 / 50)
    assert tr.attributed_share(lo, hi, [r"copy"]) == 1.0


def test_idle_gaps_named_by_innermost_serve_span():
    run, _ = hand_run()
    tr = run.trace
    lo, hi = tr.window()
    # gaps (ops 7 us late): [70, 100] and [0, 10] in the window alone,
    # [50, 60] with B3's stage open at its middle
    gaps = tr.idle_gaps(lo, hi)
    assert gaps == [["bench.window", 0.029993], ["bench.window", 0.010007],
                    ["serve.stage", 0.01]]


def test_idle_time_by_the_tracer_span_open_in_it():
    run, _ = hand_run()
    # the tracer's spans 7 us later on the trace's clock: the gap [50,
    # 60] falls in B3's stage (4 ms, shorter than its coalescing); the
    # gaps at either end of the window in no server span
    assert served.idle_by_span(run) == pytest.approx(
        {"bench.window": 0.04, "serve.stage": 0.01})
    assert served.idle_by_span(run, min_ns=20 * MS) == pytest.approx(
        {"bench.window": 0.029993, "(shorter gaps)": 0.020007})


def test_span_metrics_on_a_hand_made_run():
    run, _ = hand_run()

    def read(name):
        return CATALOG.module("metrics", name).read(run)

    # waits: 5, 2, 10 (B2 has the device from 30, not 25), 5 ms
    assert read("serving.wait_p95_ms.open") == pytest.approx(
        float(np.percentile([5, 2, 10, 5], 95)))
    # gaps: B1 end 30 -> B2 start 25 (they overlap), B2 end 50 -> B3 60
    assert read("serving.gap_ms_per_batch.closed") == pytest.approx(2.5)
    # waiting and idle: [5, 10] and [55, 60] of a 100 ms window
    assert read("device.idle_with_work_pct.open") == pytest.approx(
        10.0, abs=1e-3)
    # merge 18 ms and convert 2 ms of 50 ms busy
    assert read("device.merge_pct.open") == pytest.approx(36.0)
    assert read("device.convert_pct.open") == pytest.approx(4.0)


def test_gap_split_by_server_span():
    run, _ = hand_run()
    split = served.gap_split_ms(run)
    # B1 -> B2 overlap and leave no gap; B2 -> B3 leaves [50, 60]: d2h
    # [50, 51], fan-out [51, 52], B3's coalescing [55, 59.7], its stage
    # [54, 58] where coalescing is not, the batches' other parts
    # [52, 52.1] and [59.7, 60], and nothing at all [52.1, 54]
    assert split == pytest.approx({
        "serve.d2h": 1.0, "serve.fanout": 1.0, "serve.coalesce": 4.7,
        "serve.slot": 0.0, "serve.stage": 1.0, "serve.batch": 0.4,
        "unspanned": 1.9})


def test_span_metrics_read_nothing_without_records_or_trace():
    empty = SimpleNamespace(t0=0.0, t1=1.0, trace=None)
    for name in traced.SPAN_METRICS:
        assert CATALOG.module("metrics", name).read(empty) is None
    run, _ = hand_run()
    run.trace = None
    assert served.idle_with_work_pct(run) is None
    assert served.stage_pct(run, "scan.merge") is None
    assert served.wait_p95_ms(run) is not None


def test_scope_of_an_op_path():
    assert scope_of("jit(<lambda>)/search.scan/jit(search_flat_segmented)/"
                    "while/body/closed_call/scan.kernel/"
                    "jit(quantized_maxsim_pallas)/"
                    "quantized_maxsim_pallas_scan/pallas_call") == (
        "search.scan/scan.kernel")
    assert scope_of("jit(<lambda>)/search.rerank/gather") == "search.rerank"
    assert scope_of("jit(f)/transpose") == ""


def test_hlo_scopes_of_a_compiled_program():
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("search.scan"):
            def body(c, j):
                with jax.named_scope("scan.merge"):
                    return c + jnp.sort(x * j)[:4].sum(), None
            c, _ = jax.lax.scan(body, 0.0, jnp.arange(3.0))
        with jax.named_scope("search.rerank"):
            return jnp.cumsum(x) + c

    text = jax.jit(f).lower(jnp.ones((64,))).compile().as_text()
    scopes = hlo_scopes(text)
    assert "search.scan/scan.merge" in scopes.values()
    assert any(v.startswith("search.rerank") for v in scopes.values())
    sorts = [v for (name, shape), v in scopes.items()
             if name.startswith("sort") and shape == "f32[64]{0}"]
    assert sorts == ["search.scan/scan.merge"]


def test_an_op_event_is_looked_up_by_name_and_result_shape():
    """The chip names an op event by its instruction's text, operands
    printed with their shapes; the rungs' programs share instruction
    names, so the lookup key holds the result shape too."""
    event = ("%sort.9 = (f32[1,384]{1,0:T(1,128)}, s32[1,384]{1,0:T(1,128)}) "
             "sort(f32[1,384]{1,0:T(1,128)} %pad.3, s32[1,384]{1,0} %iota.1)"
             ", dimensions={1}")
    hlo = ("  %sort.9 = (f32[1,384]{1,0:T(1,128)}, s32[1,384]{1,0:T(1,128)}) "
           "sort(%pad.3, %iota.1), dimensions={1}, metadata={op_name="
           '"jit(f)/search.scan/while/body/closed_call/scan.merge/sort"}\n'
           "  %sort.9 = (f32[2,384]{1,0:T(2,128)}, s32[2,384]{1,0:T(2,128)}) "
           "sort(%pad.3, %iota.1), dimensions={1}, metadata={op_name="
           '"jit(f)/search.rerank/while/body/closed_call/scan.merge/sort"}')
    assert instruction(event) == (
        "sort.9", "(f32[1,384]{1,0:T(1,128)}, s32[1,384]{1,0:T(1,128)})")
    assert hlo_scopes(hlo)[instruction(event)] == "search.scan/scan.merge"
    assert instruction("jit_search(123)") == ("jit_search(123)", "")


def test_json_round_trip_and_the_unscoped_fixture(tmp_path):
    run, _ = hand_run()
    path = str(tmp_path / "t.json.gz")
    run.trace.to_json(path)
    back = ScopedTrace.from_json(path)
    assert back.ops == run.trace.ops and back.spans == run.trace.spans
    assert back.scopes == run.trace.scopes
    assert back.span_ids == run.trace.span_ids
    old = ScopedTrace.from_json(os.path.join(FIXTURE,
                                             "v5e_adc_search.json.gz"))
    assert len(old.scopes) == len(old.ops) == 19487
    assert set(old.scopes) == {""}
    assert old.span_ids == [{} for _ in old.spans]


def test_existing_metrics_read_the_same_on_a_scoped_trace():
    """Every metric that reads the trace reads bit for bit the same from
    the unscoped chip fixture loaded as a `Trace` and as a
    `ScopedTrace`."""
    path = os.path.join(FIXTURE, "v5e_adc_search.json.gz")
    cfg = CATALOG.json("configs", "colpali-hpc")
    peaks = CATALOG.json(".", "peaks")["devices"]["TPU v5 lite"]
    got = []
    for cls in (Trace, ScopedTrace):
        tr = cls.from_json(path)
        lo, hi = tr.window()
        run = SimpleNamespace(
            trace=tr, traces=[tr],
            catalog=CATALOG, config=cfg, pages=1 << 18, peaks=peaks,
            traced_spans=[(0.0, 0.1, 1, 1)], records=[
                {"result": 1, "done": 0.5, "due": 0.0}], t1=1.0)
        values = {m["name"]: CATALOG.module("metrics", m["name"]).read(run)
                  for m in CATALOG.benchmark["per_layer"]
                  if m["source"] == "device_trace"}
        values["busy_s"] = readers.busy_s(run)
        values["top_ops"] = top_ops([tr], lo, hi)
        values["idle_gaps"] = tr.idle_gaps(lo, hi)
        got.append(values)
    assert got[0] == got[1]
    assert got[0]["adc_roofline.open"] is not None
    assert got[0]["hamming_roofline"] is None


@pytest.mark.parametrize("cell", ["tiny.open", "tiny.closed"])
def test_tiny_served_run_with_the_tracer(tmp_path, cell):
    """A traced window of a tiny cell on the CPU: every answered request
    has one `serve.queue` and its batch one `serve.compute`; the offset
    to the profiler's clock is measured from the annotated spans; the
    server's stats read what the records hold; the device metrics read
    nothing (no device plane)."""
    cat = tiny_catalog(tmp_path)
    seed = 2**33 + 7
    c = run_cell.Cell(cat, cell, seed, annotate=True,
                      devices=jax.devices()[:1])
    hlo = traced.program_scopes(c.search.compiled)
    run = traced.traced_window(c, 1.0, seed, True, hlo)
    recs = run.serve_records
    answered = [r for r in run.records if "result" in r]
    assert answered
    reqs = [r for r in recs if r.name == "serve.request"]
    batches = {r.id: r for r in recs if r.name == "serve.batch"}
    computes = {}
    for r in recs:
        if r.name == "serve.compute":
            computes.setdefault(r.parent, []).append(r)
    assert len(reqs) >= len(answered)
    for r in reqs:
        assert sum(1 for q in recs if q.name == "serve.queue"
                   and q.parent == r.id) == 1
        assert len(computes[r.ids["batch"]]) == 1
        assert r.id in batches[r.ids["batch"]].ids["requests"]
    n_window = sum(1 for r in reqs if r.end_ns >= min(
        r.start_ns for r in reqs))
    assert run.server_stats["n"] <= n_window
    assert run.clock_offset_ns is not None
    assert len(run.residuals_ns) == 2 * len(batches)
    line = traced.read_metrics(cat, cat.cell(cell), run)
    if cell == "tiny.open":
        assert line["serving.wait_p95_ms.open"] > 0
    else:
        assert line["serving.gap_ms_per_batch.closed"] > 0
    for name in ("device.idle_with_work_pct.open", "device.merge_pct.open",
                 "device.convert_pct.open"):
        assert name not in line
    assert "search.scan/scan.merge" in hlo.values()
    bd = traced.breakdown(run)
    assert bd["attributed"] == 0.0 and bd["scope_ms"] == {}
    assert set(bd["host_ms_per_batch"]) == set(traced.HOST_STAGES)


def test_recorded_scoped_chip_trace():
    """One B=1 search over 2^18 pages (colpali-hpc) on a TPU v5e, served
    through a server with `Tracer(annotate=True)`, 5 ms of idle on
    either side (`traced.py --fixture`): the scan's 1,024 kernel calls
    and the rerank's one under their new names, which the ADC pattern
    still matches; nearly all device time in a program scope; the
    batch's annotated spans carrying its id."""
    import re

    tr = ScopedTrace.from_json(os.path.join(FIXTURE,
                                            "v5e_scoped_search.json.gz"))
    lo, hi = tr.window()
    busy = tr.busy(lo, hi)[0]
    assert len(tr.ops) == 19487 and busy == 124_521_741
    patterns = [CATALOG.module("kernels", k).PATTERN
                for k in ("adc", "hamming")]
    assert tr.attributed_share(lo, hi, patterns) >= 0.98
    assert tr.attributed_share(lo, hi) >= 0.98        # by scope alone
    names = [re.sub(r"[.]\d+$", "", o[0]) for o in tr.ops]
    assert names.count("quantized_maxsim_pallas_scan") == 1024
    assert names.count("quantized_maxsim_pallas_pool") == 1
    assert tr.kernel_ns(patterns[0], lo, hi) == 115_270_094
    assert tr.stage_ns("scan.merge", lo, hi) == 5_095_078
    assert tr.stage_ns("kernel.layout", lo, hi) == 2_373_463
    scopes = tr.scope_ns(lo, hi)
    assert scopes["search.scan/scan.kernel"] > 0.92 * busy
    assert 0 < scopes["search.rerank/scan.kernel"] < 0.002 * busy
    serve = {s[0]: ids for s, ids in zip(tr.spans, tr.span_ids)
             if s[0].startswith("serve.")}
    assert set(serve) == {"serve.stage", "serve.compute", "serve.d2h",
                          "serve.fanout"}
    assert len({ids["batch"] for ids in serve.values()}) == 1
    assert serve["serve.compute"]["rung"] == 1
