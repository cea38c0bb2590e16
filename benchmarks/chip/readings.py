#!/usr/bin/env python3
"""The readings a cell's limits are set from, on the chip at the cell's
own size: the program's numbers, and those of each control variant of
the plain reference put in the program's place.

  python3 benchmarks/chip/readings.py --workload <cell> --seeds 1,2,3 \
      --seconds <s> [--codebook]

For each seed, in one process: set the cell up, serve its own mix for a
short window, then compare the seeded sample of answers with the
reference (the program's reading) and compare, for the same queries,
the answers each variant of the reference computes at a lower precision
(`VARIANTS` of the cell's reference module; the control's reading).
Each codebook control fit (`refcore.CODEBOOK_CONTROLS`) is judged
beside the program's codebook. Prints one JSON line per seed:
{variant: {number: value}, "codebook_excess": {fit: value}}.

With `--codebook`, each seed only builds the index's first chunk, where
the program fits its codebook (the same fit as the cell's own build),
and prints the codebook numbers alone: a dozen seeds take a minute.
"""
from __future__ import annotations

import gc
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--codebook", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from benchmarks.chip import check, refcore, run_cell
    from benchmarks.chip.catalog import Catalog

    catalog = Catalog()
    devices = run_cell.cell_devices(catalog, args.workload)
    run_cell.enable_compile_cache()
    controls = tuple(refcore.CODEBOOK_CONTROLS)
    for seed in (int(s) for s in args.seeds.split(",")):
        if args.codebook:
            print(json.dumps({"seed": seed, "codebook_excess":
                              first_chunk_codebook(catalog, args.workload,
                                                   seed, controls)}),
                  flush=True)
            continue
        cell = run_cell.Cell(catalog, args.workload, seed, annotate=False,
                             devices=devices)
        run = cell.window(cell.mix, args.seconds, seed, False)
        reference = catalog.module("references", cell.config["reference"])
        answers, refs, rows, unanswered, excess = cell.references(
            run, seed, reference.VARIANTS, controls)
        line = {"seed": seed, "requests": len(run.records)}
        for variant, ref in refs.items():
            if variant != "reference":
                placed = check.reference_answers(ref, cell.config["top_k"])
                answers_v = [placed[r] for r in rows]
            else:
                answers_v = answers
            numbers, ok = check.compare(
                answers_v, refs["reference"], rows, limits=cell.limits(),
                unanswered=unanswered, codebook_excess=excess["program"])
            line[variant] = {k: v["value"] for k, v in numbers.items()}
            line[variant]["correct"] = ok
        line["codebook_excess"] = excess
        line["phases_s"] = cell.phases.seconds
        print(json.dumps(line), flush=True)
        del cell, run, refs
        gc.collect()
    return 0


def first_chunk_codebook(catalog, name: str, seed: int, controls):
    """The codebook numbers of the program's fit on the cell's first
    chunk of pages (as `systems/one_chip.py` builds it; a system that
    fits on a mesh is not read here), of the program's fit with one
    restart, and of the control fits."""
    import time

    from benchmarks.chip import pages as pages_mod
    from benchmarks.chip import refcore
    from repro.retrieval import Corpus, HPCConfig, Retriever

    cell = catalog.cell(name)
    config, workload = cell["config"], cell["workload"]
    spec = pages_mod.spec_from(config)
    k_bank, k_build, _, _ = pages_mod.corpus_keys(seed)
    size = pages_mod.chunk_sizes(workload["pages"],
                                 workload["chunk_pages"])[0]
    pg = pages_mod.chunk_pages(seed, spec,
                               pages_mod.make_topic_banks(k_bank, spec),
                               0, size)
    fits, t0 = {}, time.perf_counter()
    # the program as configured, and the program with one k-means
    # restart where the configuration states more: the step that would
    # shorten set-up
    for fit, restarts in (("program", config["hpc"]["kmeans_restarts"]),
                          ("program-restarts-1", 1)):
        hpc = dict(config["hpc"], kmeans_restarts=restarts)
        fits[fit] = np.asarray(Retriever(HPCConfig(**hpc)).build(
            k_build, Corpus(*pg)).codebook)
    t1 = time.perf_counter()
    del pg
    out = refcore.codebook_excess(config, seed, workload["pages"],
                                  workload["chunk_pages"], fits, controls)
    out["builds_s"], out["reference_s"] = t1 - t0, time.perf_counter() - t1
    return out


if __name__ == "__main__":
    sys.exit(main())
