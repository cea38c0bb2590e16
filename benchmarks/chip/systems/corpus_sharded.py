"""The system of a corpus that no one chip holds: the index split over a
mesh of the cell's chips, built chunk by chunk on the chip that owns each
chunk's pages, and searched on all of them at once.

`Retriever.build_sharded` takes the seeded pages as `pages.chunk_pages`
numbers them (a page's id is its place in that order, as on one chip),
each chunk made on the chip whose share holds it and dealt to the chips
in turn, chunk 0 first, so that the four encode at once; the codebook is
fit on chunk 0, as on one chip. Then `Retriever.search_sharded` is
compiled once per ladder rung with the index as an argument, served by
`AsyncRetrievalServer`.

The chunk loop repeats a few lines of `system.seeded_chunks` (the page
banks, the chunk sizes, a chunk's queries), which makes every chunk on
the default device and times each one: here each chunk is made on its
own chip and the loop does not wait for one. A later change to
`system.py` can give `seeded_chunks` a device per chunk and fold the two.
"""
from __future__ import annotations

import itertools
import time

import jax
import jax.numpy as jnp

from benchmarks.chip import pages as pages_mod
from benchmarks.chip import system

CHIPS = (4,)


def _mesh(devices):
    from repro.launch.mesh import make_mesh

    return make_mesh((len(devices),), ("data",), devices=list(devices))


def build(retriever, config: dict, seed: int, workload: dict, devices,
          phases: system.Phases):
    """Index the cell's seeded pages over a mesh of `devices` and draw its
    query pool. Phase "fit" runs until the build asks for its second
    chunk (chunk 0 made, the codebook fit on it); "generate+encode" is the
    rest, to the index's last write. Returns (state, queries)."""
    from repro.retrieval import Corpus

    t0 = time.perf_counter()
    mesh = _mesh(devices)
    chips = list(mesh.devices.flat)     # chip i holds the i-th share
    n_pages = workload["pages"]
    share = n_pages // len(chips)
    spec = pages_mod.spec_from(config)
    banks = pages_mod.make_topic_banks(pages_mod.corpus_keys(seed)[0], spec)
    banks = {d: jax.device_put(banks, d) for d in chips}
    sizes = pages_mod.chunk_sizes(n_pages, workload["chunk_pages"])
    offsets = list(itertools.accumulate([0] + sizes[:-1]))
    per_chunk = -(-workload["queries"] // len(sizes))
    by_chip = [[c for c, o in enumerate(offsets) if o // share == i]
               for i in range(len(chips))]
    order = [c for turn in itertools.zip_longest(*by_chip)
             for c in turn if c is not None]
    qparts = {}

    def chunks():
        for i, c in enumerate(order):
            if i == 1:
                phases.seconds["fit"] = time.perf_counter() - t0
            chip = chips[offsets[c] // share]
            with jax.default_device(chip):
                pg = pages_mod.chunk_pages(seed, spec, banks[chip], c,
                                           sizes[c])
                qparts[c] = pages_mod.chunk_queries(
                    seed, spec, pg, c, offsets[c], min(per_chunk, sizes[c]))
            yield offsets[c], Corpus(*pg)
            del pg

    state = retriever.build_sharded(pages_mod.corpus_keys(seed)[1],
                                    chunks(), n_docs=n_pages, mesh=mesh)
    jax.block_until_ready(state)
    phases.seconds["generate+encode"] = (time.perf_counter() - t0
                                         - phases.seconds.get("fit", 0.0))
    return state, system.query_pool([qparts[c] for c in sorted(qparts)],
                                    workload["queries"])


def compile(retriever, state, *, top_k: int, rungs, mq: int, d: int,
            devices):
    """`retriever.search_sharded` over a mesh of `devices`, compiled once
    per rung, the state an argument. Returns {rung: compiled}."""
    from repro.retrieval import Query

    mesh = _mesh(devices)
    fn = jax.jit(lambda st, q, qm, qs: retriever.search_sharded(
        st, Query(q, qm, qs), k=top_k, mesh=mesh))
    sds = jax.ShapeDtypeStruct
    return {b: fn.lower(state, sds((b, mq, d), jnp.float32),
                        sds((b, mq), jnp.bool_),
                        sds((b, mq), jnp.float32)).compile()
            for b in rungs}
