"""The system under test, driven through its public API only.

`Retriever.build` on the first chunk of seeded pages (which fits the
codebook), `Retriever.add` for every further chunk, one
`Retriever.compact`; then `Retriever.search` compiled once per ladder
rung with the index as an argument, served by `AsyncRetrievalServer`.
"""
from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
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


def build_index(retriever, config: dict, seed: int, n_pages: int,
                chunk: int, n_queries: int, phases: Phases):
    """Index n_pages seeded pages; draw n_queries queries spread over
    every chunk. Returns (state, queries (emb, mask, sal) as host
    arrays)."""
    from repro.retrieval import Corpus

    spec = pages_mod.spec_from(config)
    k_bank, k_build, _, _ = pages_mod.corpus_keys(seed)
    banks = pages_mod.make_topic_banks(k_bank, spec)
    sizes = pages_mod.chunk_sizes(n_pages, chunk)
    per_chunk = -(-n_queries // len(sizes))
    state, qparts, offset = None, [], 0
    for c, size in enumerate(sizes):
        pg = phases.run("generate", pages_mod.chunk_pages, seed, spec,
                        banks, c, size)
        qparts.append(pages_mod.chunk_queries(seed, spec, pg, c, offset,
                                              min(per_chunk, size)))
        if state is None:
            state = phases.run("build", retriever.build, k_build,
                               Corpus(*pg))
        else:
            state = phases.run("add", retriever.add, state, Corpus(*pg))
        offset += size
        del pg
    if len(sizes) > 1:
        state = phases.run("compact", retriever.compact, state)
    queries = tuple(np.concatenate([np.asarray(p[i]) for p in qparts])
                    [:n_queries] for i in range(3))
    return state, queries


def compile_search(retriever, state, *, top_k: int, rungs, mq: int, d: int):
    """`retriever.search` compiled once per rung, the state an argument.
    Returns {rung: compiled}."""
    from repro.retrieval import Query

    fn = jax.jit(lambda st, q, qm, qs: retriever.search(
        st, Query(q, qm, qs), k=top_k))
    sds = jax.ShapeDtypeStruct
    return {b: fn.lower(state, sds((b, mq, d), jnp.float32),
                        sds((b, mq), jnp.bool_),
                        sds((b, mq), jnp.float32)).compile()
            for b in rungs}


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
    """Device bytes of the index state: the sum of its leaves' nbytes."""
    return sum(int(x.nbytes) for x in jax.tree.leaves(state))
