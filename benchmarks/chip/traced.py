#!/usr/bin/env python3
"""Runs a cell with the served path's own tracer on, and reads its spans
beside the device trace.

  python3 benchmarks/chip/traced.py --workload <cell> --seed <n> \
      --seconds <s> [--untraced] [--fixture PATH]

The cell is set up as `run_cell.py` sets it up, on its chips. Its window
then runs through a server given a `repro.tracing.Tracer(annotate=True)`,
under the profiler. Every chip's trace is read as a
`spantrace.ScopedTrace`: the chip's ops with their program scopes (from
the compiled programs' HLO where the trace does not carry them), and the
server's `serve.*` spans with their ids. The tracer's records are mapped
onto the trace's clock by the offset `served.clock_offset` measures.
Every metric of the cell, and every metric whose reader is in
`SPAN_METRICS`, is read; as in `run_cell.py`, a kernel's time is summed
over the chips. The scoped breakdown, the offset and the span metrics
read the first chip's trace alone.

With `--untraced`, a window with no annotations and no profiler runs
first, on the same set-up and seed: the two windows' metrics differ by
what tracing costs. With `--fixture PATH`, no window runs: one B=1
search is traced, with 5 ms of idle on either side, and the first chip's
trace is written to PATH (`ScopedTrace.to_json`).

Prints one JSON line per window: `traced`, `metrics`, `offset_ns` and
the residuals of the matched spans, and `breakdown`: device time by
scope, the share of it attributed to a scope or a kernel, the gaps
between batches split by server span, host time per batch by span, and
the longest idle gaps named by the innermost host span.
"""
from __future__ import annotations

import asyncio
import gc
import glob
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

# readers of the program's spans and scopes (metrics/<name>.py)
SPAN_METRICS = ("serving.wait_p95_ms.open", "serving.gap_ms_per_batch.closed",
                "device.idle_with_work_pct.open", "device.merge_pct.open",
                "device.convert_pct.open")
HOST_STAGES = ("serve.coalesce", "serve.slot", "serve.stage",
               "serve.compute", "serve.d2h", "serve.fanout")


def program_scopes(compiled: dict) -> dict:
    """{HLO instruction name: scope} over the compiled rungs' programs."""
    from benchmarks.chip.spantrace import hlo_scopes

    out = {}
    for b in sorted(compiled):
        out.update(hlo_scopes(compiled[b].as_text()))
    return out


def traced_window(cell, seconds: float, seed: int, traced: bool,
                  hlo: dict):
    """One window of the cell's mix through a server with a tracer (with
    annotations and the profiler if `traced`); returns the `Run`."""
    import jax

    from benchmarks.chip import run_cell, served
    from benchmarks.chip.spantrace import ScopedTrace
    from benchmarks.chip.trace import find_profile
    from repro.serving.server import AsyncRetrievalServer
    from repro.tracing import Tracer

    out = run_cell.Run()
    out.catalog, out.config = cell.catalog, cell.config
    out.pages, out.index_bytes = cell.workload["pages"], cell.index_bytes
    out.chips = len(cell.devices)
    out.peaks = cell.catalog.json(".", "peaks")["devices"].get(
        cell.devices[0].device_kind)
    tracer = Tracer(annotate=traced)
    server = AsyncRetrievalServer(cell.served_fn, cell.serve_cfg,
                                  tracer=tracer)
    traffic = cell.catalog.module("traffic", cell.mix["driver"])
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    cell.search.annotate = traced
    cell.search.spans.clear()
    window = []

    def on_start():
        if traced:
            jax.profiler.start_trace(log_dir)
            window.append(jax.profiler.TraceAnnotation("bench.window"))
            window[0].__enter__()

    def on_end():
        if window:
            window[0].__exit__(None, None, None)

    gc.collect()
    gc.freeze()
    try:
        out.records, out.t0, out.t1, out.server_stats = run_cell.serve_window(
            server, cell.pool, traffic, cell.mix, seconds, seed, 60.0,
            on_start, on_end)
    finally:
        gc.unfreeze()
    out.gave_up, out.seconds = out.t1 + 60.0, seconds
    out.setup_s = run_cell.process_age_s() - (time.perf_counter() - out.t0)
    out.spans = list(cell.search.spans)
    out.serve_records = tracer.records()
    out.serve_counters = dict(tracer.counters)
    if traced:
        jax.profiler.stop_trace()
        out.traces = ScopedTrace.from_profile(
            find_profile(log_dir),
            [f"/device:TPU:{d.id}" for d in cell.devices], hlo)
        out.trace = out.traces[0]
        shutil.rmtree(log_dir, ignore_errors=True)
        out.traced_spans = out.spans
        got = served.clock_offset(out.serve_records, out.trace)
        if got is not None:
            out.clock_offset_ns, out.residuals_ns = got
    return out


def breakdown(run) -> dict:
    """What the per-layer metrics summarise, for PERF.md: device time of
    the first chip by scope, the top ops summed over every chip, and with
    more chips each chip's busy time."""
    from benchmarks.chip import readers, served
    from benchmarks.chip import trace as trace_mod

    recs = run.serve_records
    n_batches = sum(1 for r in recs if r.name == "serve.batch") or 1
    queue = [r.ms for r in recs if r.name == "serve.queue"]
    n_req = sum(1 for r in recs if r.name == "serve.request") or 1
    overlap, prev_end = 0, None
    for start, end, _ in served.computes(run) or ():
        if prev_end is not None:
            overlap += max(0, min(end, prev_end) - start)
        prev_end = end if prev_end is None else max(prev_end, end)
    out = {"compute_ms_per_query": sum(
        r.end_ns - r.start_ns for r in recs if r.name == "serve.compute")
        / n_req / 1e6,
        "compute_overlap_ms_per_query": overlap / n_req / 1e6,
        "host_ms_per_batch": {
        n: sum(r.end_ns - r.start_ns for r in recs if r.name == n)
        / n_batches / 1e6 for n in HOST_STAGES},
        "queue_p95_ms": float(np.percentile(queue, 95)) if queue else None,
        "gap_split_ms": served.gap_split_ms(run),
        "counters": run.serve_counters}
    if run.trace is None:
        return out
    lo, hi = run.trace.window()
    busy = run.trace.busy(lo, hi)[0]
    scopes = run.trace.scope_ns(lo, hi)
    kernels = [run.catalog.module("kernels", os.path.basename(p)[:-3])
               .PATTERN for p in glob.glob(os.path.join(
                   run.catalog.root, "kernels", "*.py"))]
    out.update({
        "busy_s": busy / 1e9, "window_s": (hi - lo) / 1e9,
        "scope_ms": {k or "(none)": v / 1e6 for k, v in sorted(
            scopes.items(), key=lambda kv: -kv[1])},
        "attributed": run.trace.attributed_share(lo, hi, kernels),
        "top_ops": trace_mod.top_ops(run.traces, lo, hi),
        "idle_gaps": run.trace.idle_gaps(lo, hi),
        "idle_s_by_span": served.idle_by_span(run)})
    if run.chips > 1:
        out["busy_s_per_chip"] = readers.busy_s_per_chip(run)
    return out


def read_metrics(catalog, cell_entry, run) -> dict:
    names = [m["name"] for m in cell_entry["end_to_end"]
             + cell_entry["per_layer"]]
    names += [n for n in SPAN_METRICS if n not in names]
    out = {}
    for name in names:
        value = catalog.module("metrics", name).read(run)
        if value is not None:
            out[name] = float(value)
    return out


def record_fixture(cell, path: str, hlo: dict) -> dict:
    """Trace one B=1 search through a traced server, 5 ms of idle on
    either side, and write the first chip's trace to `path`."""
    import jax

    from benchmarks.chip.spantrace import ScopedTrace
    from benchmarks.chip.trace import find_profile
    from repro.serving.server import AsyncRetrievalServer
    from repro.tracing import Tracer

    emb, mask, sal = cell.pool
    server = AsyncRetrievalServer(cell.served_fn, cell.serve_cfg,
                                  tracer=Tracer(annotate=True))
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")

    async def main():
        await server.start()
        await server.query(emb[0], mask[0], sal[0])
        jax.profiler.start_trace(log_dir)
        with jax.profiler.TraceAnnotation("bench.window"):
            await asyncio.sleep(0.005)
            await server.query(emb[1], mask[1], sal[1])
            await asyncio.sleep(0.005)
        jax.profiler.stop_trace()
        await server.aclose()

    asyncio.run(main())
    trace = ScopedTrace.from_profile(
        find_profile(log_dir), [f"/device:TPU:{cell.devices[0].id}"], hlo)[0]
    shutil.rmtree(log_dir, ignore_errors=True)
    trace.to_json(path)
    lo, hi = trace.window()
    return {"fixture": path, "ops": len(trace.ops),
            "bytes": os.path.getsize(path),
            "busy_ms": trace.busy(lo, hi)[0] / 1e6,
            "scope_ms": {k or "(none)": v / 1e6
                         for k, v in trace.scope_ns(lo, hi).items()}}


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--untraced", action="store_true")
    ap.add_argument("--fixture")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from benchmarks.chip import run_cell
    from benchmarks.chip.catalog import Catalog

    catalog = Catalog()
    cell_entry = catalog.cell(args.workload)
    devices = run_cell.cell_devices(catalog, args.workload)
    run_cell.enable_compile_cache()
    cell = run_cell.Cell(catalog, args.workload, args.seed, annotate=True,
                         devices=devices)
    hlo = program_scopes(cell.search.compiled)
    if args.fixture:
        print(json.dumps(record_fixture(cell, args.fixture, hlo)),
              flush=True)
        return 0
    for traced in ([False] if args.untraced else []) + [True]:
        run = traced_window(cell, args.seconds, args.seed, traced, hlo)
        res = getattr(run, "residuals_ns", None)
        line = {"workload": args.workload, "seed": args.seed,
                "traced": traced,
                "metrics": read_metrics(catalog, cell_entry, run),
                "offset_ns": getattr(run, "clock_offset_ns", None),
                "residual_ns": None if res is None else {
                    "n": len(res),
                    "median_abs": float(np.median(np.abs(res))),
                    "max_abs": float(np.max(np.abs(res)))},
                "breakdown": breakdown(run)}
        print(json.dumps(line), flush=True)
        del run
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
