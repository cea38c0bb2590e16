#!/usr/bin/env python3
"""Runs one benchmark cell of the served colpali-hpc search on the chip.

  python3 benchmarks/chip/run_cell.py --workload <cell> --seed <n> \
      --seconds <s> --trace <0|1>

Set-up builds the cell's index from `--seed` on the cell's chips (pages
made on the device; how it is built and searched is the configuration's
system, `systems/<name>.py`: on one chip `Retriever.build` / `add` /
`compact`), compiles the search once per ladder rung, serves it with
`AsyncRetrievalServer` and runs once each rung the cell's traffic uses.
The window then offers the cell's traffic for `--seconds`. After it,
the run reads the peak device memory of every chip, frees the index, and
compares a seeded sample of the served answers with the plain reference.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
`--trace 1` its per-layer metrics), `device` (every chip of the cell
read: the fullest chip's peak memory, and with `--trace 1` the busy time
averaged over the chips), with `--trace 1` `breakdown`, and last
`checks`, each number compared beside its limit.
Without a TPU, with fewer chips than the cell asks for or a count its
system does not serve, or with a chip that `peaks.json` does not list,
it exits non-zero and prints no result.
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

T_START = time.perf_counter()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def process_age_s() -> float:
    """Seconds since this process started (Linux), else since import."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_START


class RunRefused(RuntimeError):
    """The run cannot measure what it was asked to: no result."""


class Run:
    """What a finished window leaves for the metric readers."""

    chips = 1
    trace = None        # the first chip's
    traces = ()         # one a chip, in mesh order
    traced_spans = ()


def require_devices(chips: int, peaks: dict, devs=None, allowed=(1,)):
    """The first `chips` of `devs` (JAX's devices), or RunRefused: no
    TPU, a count other than 1 or 4 or than the system serves
    (`allowed`), too few chips, or a chip the peaks table does not
    list."""
    import jax

    devs = jax.devices() if devs is None else devs
    if devs[0].platform != "tpu":
        raise RunRefused(f"JAX found no TPU (platform {devs[0].platform!r})")
    if chips not in (1, 4):
        raise RunRefused(f"the cell asks for {chips} chips; a cell takes "
                         "1 or 4")
    if chips not in allowed:
        raise RunRefused(f"the cell asks for {chips} chips; its system "
                         f"serves on {tuple(allowed)}")
    if len(devs) < chips:
        raise RunRefused(f"the cell asks for {chips} chips, JAX found "
                         f"{len(devs)}")
    for d in devs[:chips]:
        if d.device_kind not in peaks["devices"]:
            raise RunRefused(f"no peaks for device kind "
                             f"{d.device_kind!r} in peaks.json")
    return devs[:chips]


def cell_devices(catalog, name: str, devs=None):
    """`require_devices` for cell `name`: the chips it asks for, as its
    configuration's system serves them."""
    cell = catalog.cell(name)
    return require_devices(cell["entry"]["chips"], catalog.json(".", "peaks"),
                           devs, allowed=catalog.system(cell["config"]).CHIPS)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: $JAX_COMPILATION_CACHE_DIR if
    set, else the fixed directory `.jax_cache` at the checkout's root.
    Every program is cached, so only a checkout's first run compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def serve_window(server, pool, driver, mix, seconds, seed, give_up_s,
                 on_start, on_end):
    """Run the traffic for one window on a fresh event loop. Returns
    (records, window start, window end, server stats at the end)."""
    import asyncio

    emb, mask, sal = pool

    async def query(i):
        return await server.query(emb[i], mask[i], sal[i])

    async def main():
        await server.start()
        server.reset_stats()
        on_start()
        t0 = time.perf_counter()
        task = asyncio.create_task(driver.drive(
            query, len(emb), mix, t0, seconds, seed, give_up_s))
        await asyncio.sleep(max(0.0, t0 + seconds - time.perf_counter()))
        stats = server.stats()
        on_end()
        records = await task
        await server.aclose()
        return records, t0, t0 + seconds, stats

    return asyncio.run(main())


class Cell:
    """A cell's system under test, set up on its chips (`devices`, as
    `cell_devices` picks them): the index built
    from the seed and the search compiled per ladder rung by the
    configuration's system, and the rungs the cell's traffic uses
    (`warm_rungs` of its workload file) run once.

    `wrap_search(fn) -> fn`, if given, wraps the served search function
    (the tests plant faults with it)."""

    def __init__(self, catalog, name: str, seed: int, *, annotate: bool,
                 devices, wrap_search=None):
        from benchmarks.chip import system
        from repro.retrieval import HPCConfig, Retriever
        from repro.serving.server import ServeConfig

        self.catalog, self.name, self.seed = catalog, name, seed
        cell = catalog.cell(name)
        self.cell, self.config = cell, cell["config"]
        self.workload, self.mix = cell["workload"], cell["mix"]
        self.devices = list(devices)
        enc = self.config["encoder"]
        retriever = Retriever(HPCConfig(**self.config["hpc"]))
        self.serve_cfg = ServeConfig(max_batch=self.config["max_batch"],
                                     top_k=self.config["top_k"])
        self.phases = system.Phases()
        system_mod = catalog.system(self.config)
        self.state, self.pool = system_mod.build(
            retriever, self.config, seed, self.workload, self.devices,
            self.phases)
        t0 = time.perf_counter()
        compiled = system_mod.compile(
            retriever, self.state, top_k=self.config["top_k"],
            rungs=self.serve_cfg.resolved_ladder(), mq=enc["query_len"],
            d=enc["proj_dim"], devices=self.devices)
        self.phases.seconds["compile"] = time.perf_counter() - t0
        self.search = system.SearchSpans(compiled, self.state, annotate)
        self.served_fn = (wrap_search(self.search) if wrap_search
                          else self.search)
        t0 = time.perf_counter()
        q = [a[0] for a in self.pool]
        for b in self.workload["warm_rungs"]:
            self.served_fn(*(np.broadcast_to(a, (b,) + a.shape) for a in q))
        self.phases.seconds["warm-up"] = time.perf_counter() - t0
        self.search.spans.clear()
        self.index_bytes = system.resident_bytes(self.state)

    def window(self, mix: dict, seconds: float, seed: int,
               trace: bool) -> Run:
        """Serve `mix` for one window through a new server; with `trace`,
        read every chip's trace."""
        import gc
        import shutil
        import tempfile

        import jax

        from benchmarks.chip import trace as trace_mod
        from repro.serving.server import AsyncRetrievalServer

        out = Run()
        out.catalog, out.config = self.catalog, self.config
        out.pages, out.index_bytes = self.workload["pages"], self.index_bytes
        out.chips = len(self.devices)
        out.peaks = self.catalog.json(".", "peaks")["devices"].get(
            self.devices[0].device_kind)
        server = AsyncRetrievalServer(self.served_fn, self.serve_cfg)
        driver = self.catalog.module("traffic", mix["driver"])
        log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        window = []
        self.search.spans.clear()

        def on_start():
            if trace:
                jax.profiler.start_trace(log_dir)
                window.append(jax.profiler.TraceAnnotation("bench.window"))
                window[0].__enter__()

        def on_end():
            if window:
                window[0].__exit__(None, None, None)

        give_up_s = 60.0
        # set-up's objects are not scanned again by a collection in the
        # window, so the loop that sends the traffic does not stall on one
        gc.collect()
        gc.freeze()
        try:
            out.records, out.t0, out.t1, out.server_stats = serve_window(
                server, self.pool, driver, mix, seconds, seed, give_up_s,
                on_start, on_end)
        finally:
            gc.unfreeze()
        out.gave_up, out.seconds = out.t1 + give_up_s, seconds
        out.setup_s = process_age_s() - (time.perf_counter() - out.t0)
        out.spans = list(self.search.spans)
        if trace:
            jax.profiler.stop_trace()
            out.traces = trace_mod.Trace.from_profile(
                trace_mod.find_profile(log_dir),
                [f"/device:TPU:{d.id}" for d in self.devices])
            out.trace = out.traces[0]
            shutil.rmtree(log_dir, ignore_errors=True)
            out.traced_spans = out.spans
        return out

    def references(self, run: Run, seed: int, variants=("reference",),
                   controls=()):
        """Free the index, then run the plain reference (and the named
        variants of it) for a seeded sample of the window's answers, and
        judge the program's codebook (and the named control fits of
        `refcore.CODEBOOK_CONTROLS`) against a plain k-means. Returns
        (answers, {variant: reference arrays}, reference rows, unanswered
        count, {fit: codebook excess})."""
        import gc

        from benchmarks.chip import refcore
        from repro.serving.server import Served

        codebook = np.asarray(self.state.codebook)
        del self.state, self.search, self.served_fn
        gc.collect()
        served = [r for r in run.records
                  if isinstance(r.get("result"), Served)]
        rng = np.random.default_rng([seed, 4])
        n = min(len(served), self.workload["check_requests"])
        sample = [served[i] for i in sorted(
            rng.choice(len(served), n, replace=False).tolist())]
        qs = sorted({r["q"] for r in sample})
        row = {q: j for j, q in enumerate(qs)}
        reference = self.catalog.module("references",
                                        self.config["reference"])
        pages, chunk = self.workload["pages"], self.workload["chunk_pages"]
        t0 = time.perf_counter()
        refs = {v: reference.scores(
            self.config, self.seed, pages, chunk, codebook,
            (self.pool[0][qs], self.pool[1][qs]), variant=v)
            for v in variants}
        excess = refcore.codebook_excess(self.config, self.seed, pages,
                                         chunk, {"program": codebook},
                                         controls)
        self.phases.seconds["reference (after the window)"] = (
            time.perf_counter() - t0)
        return ([r["result"] for r in sample], refs,
                [row[r["q"]] for r in sample],
                len(run.records) - len(served), excess)

    def check(self, run: Run, seed: int):
        """The comparison that decides `correct`: (checks, correct)."""
        from benchmarks.chip import check

        answers, refs, rows, unanswered, excess = self.references(run, seed)
        return check.compare(answers, refs["reference"], rows,
                             limits=self.limits(), unanswered=unanswered,
                             codebook_excess=excess["program"])

    def limits(self) -> dict:
        """The limits, set from readings, of the numbers compared that
        are not exact: the score gap and the codebook's excess
        distortion (the cell's reference module holds them)."""
        return dict(self.catalog.module("references",
                                        self.config["reference"]).LIMITS)


def run(catalog, name: str, seed: int, seconds: float, trace: bool,
        devices, *, wrap_search=None):
    """One run of cell `name` on `devices`, the chips it builds and
    serves on; returns the result dict."""
    from benchmarks.chip import readers
    from benchmarks.chip import trace as trace_mod

    cell = Cell(catalog, name, seed, annotate=trace, devices=devices,
                wrap_search=wrap_search)
    out = cell.window(cell.mix, seconds, seed, trace)
    memory = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in cell.devices]
    checks, correct = cell.check(out, seed)
    metrics = {}
    for m in cell.cell["per_layer" if trace else "end_to_end"]:
        value = catalog.module("metrics", m["name"]).read(out)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    first = cell.devices[0]
    dev = {"platform": first.platform, "kind": first.device_kind,
           "count": len(cell.devices),
           "memory_peak_bytes": max((m for m in memory if m is not None),
                                    default=None),
           "memory_peak_bytes_per_chip": memory}
    result = {"correct": correct, "attempted": len(out.records),
              "failed": checks["unanswered"]["value"], "metrics": metrics,
              "device": dev}
    if trace:
        lo, hi = out.trace.window()
        busy = readers.busy_s_per_chip(out)
        dev["busy_s"] = sum(busy) / len(busy)
        dev["busy_s_per_chip"] = busy
        dev["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {
            "device_ops": trace_mod.top_ops(out.traces, lo, hi),
            "idle_gaps": out.trace.idle_gaps(lo, hi)}
    result["setup_phases_s"] = cell.phases.seconds
    result["checks"] = checks        # last: the numbers beside their limits
    return result


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from benchmarks.chip.catalog import Catalog

        catalog = Catalog()
        devices = cell_devices(catalog, args.workload)
        enable_compile_cache()
        result = run(catalog, args.workload, args.seed, args.seconds,
                     bool(args.trace), devices)
    except (RunRefused, ImportError, OSError, KeyError) as e:
        print(f"run_cell: no result: {e!r}", file=sys.stderr, flush=True)
        return 2
    for name, v in result["checks"].items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
