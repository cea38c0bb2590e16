"""Retrieval serving CLI: build an HPC-ColPali index over a synthetic
corpus and serve batched queries through the asyncio continuous-batching
server (power-of-two padding ladder).

  PYTHONPATH=src python -m repro.launch.serve --n-docs 4096 --queries 256 \
      --backend flat --k 256 --p 60
  PYTHONPATH=src python -m repro.launch.serve --arch colpali-hpc \
      --pages 65536 --queries 64 --rate-qps 20

`--backend` names a registry backend (float_flat / flat / ivf / hnsw /
hamming);
the deprecated `--mode`/`--index` pair is still accepted. `--rate-qps`
switches from closed-loop (submit everything at once) to an open-loop
Poisson arrival process; `--single-shape` disables the padding ladder
(v1 behaviour: every batch pads to --max-batch).

`--arch colpali-hpc` serves the paper's deployment at its own widths and
knobs (`configs/colpali_hpc.py`): D=128, 1024 patches per page pruned at
p=60, K=256, 32 query tokens, `flat` with rerank 32, top-k 128, ladder up
to 64 queries. Its index is built chunk by chunk (`build_pages`) — the
float corpus (512 KB per page) is never whole on the device. The search
is compiled per ladder rung with the index as an argument
(`compile_search`), never as a compile-time constant. `chip_smoke.py`
runs the same functions on the chip.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.index import IVFConfig
from repro.data import synthetic
from repro.launch.cache import enable_compile_cache
from repro.retrieval import (Corpus, HPCConfig, Query, Retriever,
                             available_backends)
from repro.serving.client import drive
from repro.serving.server import AsyncRetrievalServer, ServeConfig


PAGE_CHUNK = 4096    # pages generated and indexed per step (2 GB of f32)


def page_spec(arch) -> synthetic.CorpusSpec:
    """The synthetic page model at a deployment's encoder widths."""
    enc = arch.encoder
    return dataclasses.replace(synthetic.VIDORE, n_patches=enc.n_patches,
                               n_q_patches=enc.query_len, dim=enc.proj_dim)


def build_pages(retriever: Retriever, key, spec: synthetic.CorpusSpec,
                n_pages: int, n_queries: int, *,
                budget_s=None, min_pages: int = 0):
    """Index `n_pages` seeded pages, `PAGE_CHUNK` pages at a time.

    `Retriever.build` on the first chunk (which also fits the codebook),
    `Retriever.add` for every further chunk, one `Retriever.compact` to
    fold the segments. Queries are drawn from the first chunk's pages.
    Once `budget_s` seconds have passed and at least `min_pages` are in,
    the build stops early.

    Returns (state, queries, target ids, pages indexed, stop reason).
    """
    chunk = min(PAGE_CHUNK, n_pages)
    k_bank, k_build, k_query, k_chunks = jax.random.split(key, 4)
    banks = synthetic.make_topic_banks(k_bank, spec)
    t0 = time.perf_counter()
    first = synthetic.make_page_chunk(jax.random.fold_in(k_chunks, 0), banks,
                                      spec, chunk)
    targets = jnp.arange(n_queries, dtype=jnp.int32) * max(
        1, chunk // n_queries) % chunk
    queries = Query(*synthetic.make_page_queries(k_query, first[0], targets,
                                                 spec))
    state = retriever.build(k_build, Corpus(*first))
    del first
    pages, reason = chunk, "target reached"
    while pages < n_pages:
        if (budget_s is not None and pages >= min_pages
                and time.perf_counter() - t0 > budget_s):
            reason = f"build budget of {budget_s:.0f} s spent"
            break
        size = min(chunk, n_pages - pages)
        delta = synthetic.make_page_chunk(
            jax.random.fold_in(k_chunks, pages // chunk), banks, spec, size)
        state = retriever.add(state, Corpus(*delta))
        pages += size
    if pages > chunk:
        state = retriever.compact(state)
    jax.block_until_ready(state)
    return state, queries, np.asarray(targets), pages, reason


def compile_search(retriever: Retriever, state, *, top_k: int, rungs,
                   mq: int, d: int):
    """Compile `retriever.search` once per ladder rung, state an argument.

    Returns (search_fn(q, qm, qs) for the server, {rung: compiled}).
    """
    fn = jax.jit(lambda st, q, qm, qs: retriever.search(
        st, Query(q, qm, qs), k=top_k))
    sds = jax.ShapeDtypeStruct
    compiled = {b: fn.lower(state, sds((b, mq, d), jnp.float32),
                            sds((b, mq), jnp.bool_),
                            sds((b, mq), jnp.float32)).compile()
                for b in rungs}

    def search_fn(q, qm, qs):
        return compiled[q.shape[0]](state, q, qm, qs)

    return search_fn, compiled


def serve_requests(server, queries: Query, n_requests: int,
                   rate_qps: float, seed: int = 1):
    """Submit `n_requests` through the server on a fresh event loop, then
    close it; returns (outcomes in submission order, exceptions in place,
    wall seconds)."""
    host = [np.asarray(a) for a in queries]

    async def run():
        t0 = time.perf_counter()
        results = await drive(server, *host, n_requests=n_requests,
                              rate_qps=rate_qps, seed=seed,
                              return_exceptions=True)
        wall = time.perf_counter() - t0
        await server.aclose()
        return results, wall

    return asyncio.run(run())


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=["colpali-hpc"],
                    help="serve a deployment at its configured widths")
    ap.add_argument("--pages", type=int, default=65536,
                    help="--arch: pages to index")
    ap.add_argument("--n-docs", type=int, default=4096)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--backend", default=None,
                    choices=list(available_backends()),
                    help="index backend (wins over --mode/--index)")
    ap.add_argument("--mode", default=None,
                    choices=["float", "quantized", "binary"],
                    help="deprecated: use --backend")
    ap.add_argument("--index", default=None, choices=["flat", "ivf"],
                    help="deprecated: use --backend")
    ap.add_argument("--k", type=int, default=256)
    ap.add_argument("--p", type=float, default=60.0)
    ap.add_argument("--top-k", type=int, default=10)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--rate-qps", type=float, default=0.0,
                    help="open-loop Poisson arrival rate (0 = closed loop)")
    ap.add_argument("--single-shape", action="store_true",
                    help="v1 behaviour: pad every batch to --max-batch")
    args = ap.parse_args(argv)
    enable_compile_cache()

    k_data, k_build = jax.random.split(jax.random.PRNGKey(0))
    t0 = time.perf_counter()
    if args.arch == "colpali-hpc":
        from repro.configs.colpali_hpc import COLPALI_HPC
        arch = COLPALI_HPC.config
        cfg, top_k, max_batch = arch.hpc, arch.top_k, arch.serve_queries
        spec = page_spec(arch)
        retriever = Retriever(cfg)
        state, queries, targets, pages, _ = build_pages(
            retriever, k_build, spec, args.pages, args.queries)
        relevant = [np.array([t]) for t in targets]
        print(f"indexed {pages} pages")
    else:
        spec = synthetic.CorpusSpec(n_docs=args.n_docs,
                                    n_queries=args.queries)
        data = synthetic.make_retrieval_corpus(k_data, spec)
        backend = args.backend
        if backend is None and args.mode is None and args.index is None:
            backend = "flat"
        cfg = HPCConfig(k=args.k, p=args.p, backend=backend, mode=args.mode,
                        index=args.index, prune_side="doc", rerank=32,
                        ivf=IVFConfig(n_list=64, n_probe=8))
        top_k, max_batch = args.top_k, args.max_batch
        retriever = Retriever(cfg)
        state = retriever.build(k_build, Corpus(
            data.doc_patches, data.doc_mask, data.doc_salience))
        queries = Query(data.query_patches, data.query_mask,
                        data.query_salience)
        relevant = [np.flatnonzero(np.asarray(r) > 0)
                    for r in data.relevance]
    jax.block_until_ready(state)
    print(f"index[{cfg.backend}] built in {time.perf_counter()-t0:.2f}s | "
          f"storage {retriever.storage_bytes(state)}")

    serve_cfg = ServeConfig(max_batch=max_batch, top_k=top_k,
                            ladder=(max_batch,) if args.single_shape
                            else None)
    t0 = time.perf_counter()
    search_fn, _ = compile_search(
        retriever, state, top_k=top_k, rungs=serve_cfg.resolved_ladder(),
        mq=spec.n_q_patches, d=spec.dim)
    server = AsyncRetrievalServer(search_fn, serve_cfg)
    print(f"ladder {server.ladder} compiled in "
          f"{time.perf_counter()-t0:.2f}s")
    # run every ladder rung once (excluded from the serving-window stats)
    t0 = time.perf_counter()
    server.warm_shapes(queries.embeddings[0], queries.mask[0],
                       queries.salience[0])
    print(f"ladder warmed in {time.perf_counter()-t0:.2f}s")

    results, wall = serve_requests(server, queries, args.queries,
                                   args.rate_qps)
    hits = 0
    for i, res in enumerate(results):
        if isinstance(res, BaseException):
            raise res
        hits += int(np.isin(res[1], relevant[i % len(relevant)]).any())
    st = server.stats()
    rungs = " ".join(f"B={b}:{v['batches']}x@{v['occupancy']:.2f}"
                     for b, v in st["rungs"].items())
    print(f"served {args.queries} queries in {wall:.2f}s "
          f"({st['qps']:.1f} QPS) | hit@{top_k} "
          f"{hits/args.queries:.3f} | p50 {st['p50_ms']:.1f}ms "
          f"p99 {st['p99_ms']:.1f}ms | mean batch {st['mean_batch']:.1f}")
    print(f"ladder occupancy: {rungs}")


if __name__ == "__main__":
    main()
