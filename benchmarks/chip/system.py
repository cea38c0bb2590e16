"""What every system under test shares, driven through the program's
public API only: the set-up's timed phases, the cell's seeded pages and
queries, the served search function with its spans, and the index's
device bytes. How the index is built and searched on the cell's chips is
the configuration's system, `systems/<name>.py` (see `systems/one_chip.py`).
"""
from __future__ import annotations

import threading
import time

import jax
import numpy as np

from benchmarks.chip import pages as pages_mod


class Phases:
    """Wall seconds of the named set-up phases, in order."""

    def __init__(self):
        self.seconds = {}

    def run(self, name, fn, *args, **kw):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        jax.block_until_ready(out)
        self.seconds[name] = self.seconds.get(name, 0.0) + (
            time.perf_counter() - t0)
        return out


def seeded_chunks(config: dict, seed: int, workload: dict, phases: Phases):
    """Yield the cell's seeded pages chunk by chunk (`pages.chunk_pages`;
    a page's id is its place in this order), each made on the device in
    phase "generate", with the queries drawn from that chunk: (pages,
    queries). The queries are spread over every chunk; `query_pool`
    joins them."""
    spec = pages_mod.spec_from(config)
    k_bank = pages_mod.corpus_keys(seed)[0]
    banks = pages_mod.make_topic_banks(k_bank, spec)
    sizes = pages_mod.chunk_sizes(workload["pages"], workload["chunk_pages"])
    per_chunk = -(-workload["queries"] // len(sizes))
    offset = 0
    for c, size in enumerate(sizes):
        pg = phases.run("generate", pages_mod.chunk_pages, seed, spec,
                        banks, c, size)
        yield pg, pages_mod.chunk_queries(seed, spec, pg, c, offset,
                                          min(per_chunk, size))
        offset += size
        del pg


def query_pool(qparts: list, n_queries: int):
    """The first n_queries of the queries `seeded_chunks` drew, chunk by
    chunk in `qparts`, as host arrays (embeddings, mask, salience)."""
    return tuple(np.concatenate([np.asarray(p[i]) for p in qparts])
                 [:n_queries] for i in range(3))


class SearchSpans:
    """The server's search function, with a span around each call.

    Each call ends at `block_until_ready`, so a span covers the device
    work of its batch; it records (start, end, real queries, rung). With
    `annotate`, each call is also a `bench.search` host span in the
    profiler's trace.
    """

    def __init__(self, compiled: dict, state, annotate: bool):
        self.compiled, self.state, self.annotate = compiled, state, annotate
        self.spans = []
        self._lock = threading.Lock()

    def __call__(self, q, qm, qs):
        rung = q.shape[0]
        t0 = time.perf_counter()
        if self.annotate:
            with jax.profiler.TraceAnnotation("bench.search"):
                out = self.compiled[rung](self.state, q, qm, qs)
                jax.block_until_ready(out)
        else:
            out = self.compiled[rung](self.state, q, qm, qs)
            jax.block_until_ready(out)
        t1 = time.perf_counter()
        real = int(np.asarray(qm).any(axis=1).sum())
        with self._lock:
            self.spans.append((t0, t1, real, rung))
        return out


def resident_bytes(state) -> int:
    """Device bytes of the index state on the cell's chips: the bytes of
    every shard of every leaf, so a leaf replicated on four chips counts
    four times and a leaf split over them once."""
    return sum(int(s.data.nbytes) for x in jax.tree.leaves(state)
               for s in x.addressable_shards)
