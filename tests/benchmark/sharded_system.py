"""A system for the tiny CPU cells that keeps the index on a mesh of every
device it is given: the seeded pages in chunk order, one
`Retriever.build(mesh=)`, `Retriever.shard`, and `search_sharded`
compiled once per rung. `bench_tiny.tiny_catalog(system="sharded")`
copies it in as `systems/sharded.py`."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip import pages as pages_mod
from benchmarks.chip import system

CHIPS = (1, 4)


def _mesh(devices):
    from repro.launch.mesh import make_mesh

    return make_mesh((len(devices),), ("data",), devices=list(devices))


def build(retriever, config: dict, seed: int, workload: dict, devices,
          phases: system.Phases):
    from repro.retrieval import Corpus

    chunks, qparts = zip(*system.seeded_chunks(config, seed, workload,
                                               phases))
    corpus = Corpus(*(jnp.concatenate([c[i] for c in chunks])
                      for i in range(3)))
    mesh = _mesh(devices)
    state = phases.run("build", retriever.build,
                       pages_mod.corpus_keys(seed)[1], corpus, mesh=mesh)
    state = phases.run("shard", retriever.shard, state, mesh)
    return state, system.query_pool(qparts, workload["queries"])


def compile(retriever, state, *, top_k: int, rungs, mq: int, d: int,
            devices):
    from repro.retrieval import Query

    mesh = _mesh(devices)
    fn = jax.jit(lambda st, q, qm, qs: retriever.search_sharded(
        st, Query(q, qm, qs), k=top_k, mesh=mesh))
    sds = jax.ShapeDtypeStruct
    return {b: fn.lower(state, sds((b, mq, d), jnp.float32),
                        sds((b, mq), jnp.bool_),
                        sds((b, mq), jnp.float32)).compile()
            for b in rungs}
