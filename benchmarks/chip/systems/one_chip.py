"""The system a configuration runs when it names none: the index built
and searched on one chip.

`Retriever.build` on the first chunk of seeded pages (which fits the
codebook), `Retriever.add` for every further chunk, one
`Retriever.compact`; then `Retriever.search` compiled once per ladder
rung with the index as an argument, served by `AsyncRetrievalServer`.

A system module (`systems/<name>.py`, named by a configuration's
`"system"`) provides:

  CHIPS                 the chip counts it serves
  build(retriever, config, seed, workload, devices, phases)
                        -> (index state, query pool as host arrays)
  compile(retriever, state, *, top_k, rungs, mq, d, devices)
                        -> {rung: compiled search(state, q, q_mask, q_sal)}

A page's id is global: its place in the chunk order in which
`pages.chunk_pages` numbers the corpus (`system.seeded_chunks`), so the
one plain reference (`references/`, `refcore.py`) judges the answers of
every system, on however many chips it keeps the index.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip import pages as pages_mod
from benchmarks.chip import system

CHIPS = (1,)


def build(retriever, config: dict, seed: int, workload: dict, devices,
          phases: system.Phases):
    """Index the cell's seeded pages on the default device, which has to
    be the one of `devices`, and draw its query pool. Returns (state,
    queries)."""
    from repro.retrieval import Corpus

    assert list(devices) == jax.devices()[:1], devices
    k_build = pages_mod.corpus_keys(seed)[1]
    state, qparts = None, []
    for pg, queries in system.seeded_chunks(config, seed, workload, phases):
        qparts.append(queries)
        if state is None:
            state = phases.run("build", retriever.build, k_build,
                               Corpus(*pg))
        else:
            state = phases.run("add", retriever.add, state, Corpus(*pg))
        del pg
    if len(qparts) > 1:
        state = phases.run("compact", retriever.compact, state)
    return state, system.query_pool(qparts, workload["queries"])


def compile(retriever, state, *, top_k: int, rungs, mq: int, d: int,
            devices):
    """`retriever.search` compiled once per rung for the default device,
    the one of `devices`, the state an argument. Returns {rung:
    compiled}."""
    from repro.retrieval import Query

    assert list(devices) == jax.devices()[:1], devices

    fn = jax.jit(lambda st, q, qm, qs: retriever.search(
        st, Query(q, qm, qs), k=top_k))
    sds = jax.ShapeDtypeStruct
    return {b: fn.lower(state, sds((b, mq, d), jnp.float32),
                        sds((b, mq), jnp.bool_),
                        sds((b, mq), jnp.float32)).compile()
            for b in rungs}
