#!/usr/bin/env python3
"""Smoke test of the colpali-hpc search path on a TPU.

Drives the paper's deployment (`configs/colpali_hpc.py`) once, end to
end, through the entry points a user calls — `Retriever` builds the
index, `AsyncRetrievalServer` serves it — at the deployment's widths,
with pages made on the device from a seed:

  1. build: `Retriever.build` on a first chunk of pages, `Retriever.add`
     for each further chunk, `Retriever.compact` at the end; the float
     corpus (512 KB a page) is never whole on the device. Target 2**20
     pages; if the build budget runs out first the count reached is
     printed with the reason, never fewer than 2**18;
  2. compile: the search once per ladder rung, the index an argument;
     each must hold the Pallas ADC kernel (`tpu_custom_call`);
  3. serve: run every rung once, then answer requests open-loop; every
     response must be a result, not a forwarded exception;
  4. check: the served results against the same `Retriever.search` with
     `scan_impl="jnp"` on the same index, on the chip — ids equal up to
     ties within the score tolerance, scores within it, the last tie
     group a small share of the ranks, and a planted wrong answer
     rejected; each query's source page among its results.

  python3 chip_smoke.py             # one chip
  python3 chip_smoke.py --chips 4   # only the corpus-sharded path:
      # Retriever.build(mesh=...) + shard + search_sharded on four chips,
      # against Retriever.build + search of the same pages on one device

The numbers printed are smoke numbers, not benchmark numbers. The last
line is one JSON object naming the device. Without a TPU, or when any
phase or check fails, the script exits non-zero without that line;
there is no CPU fallback.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

TARGET_PAGES = 1 << 20
MIN_PAGES = 1 << 18
BUILD_BUDGET_S = 480.0
N_QUERIES = 16             # distinct queries, drawn from the first chunk
N_REQUESTS = 48
RATE_QPS = 16.0
FOUR_CHIP_PAGES = 2048
# scores are sums of 32 table entries in [-1, 1]: 1e-5 relative is ~100
# f32 ulps, room for the kernel's and XLA's different summation orders
RTOL, ATOL = 1e-5, 1e-5
MAX_EXEMPT_SHARE = 1 / 16  # of the top-k ranks, for the last tie group
# a query is made from its source page's salient patches; a scan that
# skipped or mislaid blocks would lose those pages
MIN_SOURCE_HITS = 0.9


def log(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


class SmokeFailure(RuntimeError):
    pass


def tpu_devices(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(f"JAX found no TPU (platform "
                           f"{devs[0].platform!r}); nothing was run")
    if len(devs) < n:
        raise SmokeFailure(f"{n} chips asked for, {len(devs)} present")
    return devs[:n]


def compare(scores, ids, ref_scores, ref_ids):
    """Rank-aligned agreement with a reference top-k.

    Scores must agree within RTOL/ATOL. Ids must agree as sets over
    every run of ranks whose reference scores lie within that tolerance
    of each other (a tie group). The last group is exempt, since its
    members may continue past rank k, so it may cover at most
    MAX_EXEMPT_SHARE of the ranks: where a row is one tie group, no id
    would be checked. Returns (ok, exact id share, max score error,
    widest exempt group).
    """
    import numpy as np
    s, i = np.asarray(scores), np.asarray(ids)
    rs, ri = np.asarray(ref_scores), np.asarray(ref_ids)
    tol = ATOL + RTOL * np.abs(rs)
    err = np.abs(s - rs)
    ok = bool(np.all(np.isfinite(s)) and np.all(err <= tol))
    exempt = 0
    for row in range(rs.shape[0]):
        cuts = np.flatnonzero(np.abs(np.diff(rs[row])) > tol[row, 1:]) + 1
        groups = np.split(np.arange(rs.shape[1]), cuts)
        exempt = max(exempt, len(groups[-1]))
        for group in groups[:-1]:
            ok &= set(i[row, group].tolist()) == set(ri[row, group].tolist())
    ok &= exempt <= MAX_EXEMPT_SHARE * rs.shape[1]
    return ok, float(np.mean(i == ri)), float(err.max()), exempt


def check_against(scores, ids, ref_scores, ref_ids, what: str) -> str:
    """`compare`, plus a planted control it must reject: the reference's
    ids shifted by one rank. Raises SmokeFailure; returns a summary."""
    import numpy as np
    ok, exact, max_err, exempt = compare(scores, ids, ref_scores, ref_ids)
    summary = (f"ids identical at {exact:.4f} of ranks, max |score diff| "
               f"{max_err:.3g} (tolerance {RTOL} rel + {ATOL} abs), widest "
               f"exempt last tie group {exempt} of {np.shape(ids)[1]} ranks")
    if not ok:
        raise SmokeFailure(f"{what}: {summary}")
    if compare(scores, ids, ref_scores, np.roll(ref_ids, 1, axis=1))[0]:
        raise SmokeFailure(f"{what}: the check accepts reference ids "
                           f"shifted by one rank, so it cannot see a wrong "
                           f"id")
    return summary + "; control (ids shifted one rank) rejected"


def cache_note(path: str) -> str:
    """The compile cache's directory and whether this run starts warm."""
    n = len(os.listdir(path)) if os.path.isdir(path) else 0
    return f"compile cache {path}: {n} entries before this run"


def resident_bytes(state) -> int:
    import jax
    return sum(int(x.nbytes) for x in jax.tree.leaves(state))


def one_chip(seed: int):
    import jax
    import numpy as np

    from repro.configs.colpali_hpc import COLPALI_HPC
    from repro.core import scan
    from repro.launch import serve
    from repro.launch.cache import enable_compile_cache
    from repro.retrieval import Retriever
    from repro.serving.server import AsyncRetrievalServer, ServeConfig, Served

    dev = tpu_devices(1)[0]
    log(cache_note(enable_compile_cache()))
    if scan.resolve_impl("auto") != "pallas":
        raise SmokeFailure("the scan engine does not pick the Pallas kernel")
    arch = COLPALI_HPC.config
    enc, cfg, top_k = arch.encoder, arch.hpc, arch.top_k
    spec = serve.page_spec(arch)
    r = Retriever(cfg)

    t0 = time.perf_counter()
    state, queries, targets, pages, reason = serve.build_pages(
        r, jax.random.PRNGKey(seed), spec, TARGET_PAGES, N_QUERIES,
        budget_s=BUILD_BUDGET_S, min_pages=MIN_PAGES)
    build_s = time.perf_counter() - t0
    bs = state.backend_state
    md = (bs.segments[0] if hasattr(bs, "segments") else bs).codes.shape[1]
    log(f"pages {pages} of {TARGET_PAGES} ({reason}); {enc.n_patches} "
        f"patches/page pruned to {md} codes, K={cfg.k}, D={enc.proj_dim}")
    log(f"storage_bytes {r.storage_bytes(state)}")
    log(f"index bytes on device {resident_bytes(state)} "
        f"({resident_bytes(state) / pages:.0f} B/page)")
    log(f"build {build_s:.1f} s")

    serve_cfg = ServeConfig(max_batch=arch.serve_queries, top_k=top_k)
    t0 = time.perf_counter()
    search_fn, compiled = serve.compile_search(
        r, state, top_k=top_k, rungs=serve_cfg.resolved_ladder(),
        mq=enc.query_len, d=enc.proj_dim)
    log(f"compile {time.perf_counter() - t0:.1f} s for rungs "
        f"{sorted(compiled)}")
    missing = [b for b, c in compiled.items()
               if "tpu_custom_call" not in c.as_text()]
    if missing:
        raise SmokeFailure(f"no Pallas kernel in the search for rungs "
                           f"{missing}")

    server = AsyncRetrievalServer(search_fn, serve_cfg)
    t0 = time.perf_counter()
    server.warm_shapes(queries.embeddings[0], queries.mask[0],
                       queries.salience[0])
    log(f"warm-up {time.perf_counter() - t0:.1f} s (one call per rung)")
    results, wall = serve.serve_requests(server, queries, N_REQUESTS,
                                         RATE_QPS)
    failed = [x for x in results if not isinstance(x, Served)]
    if failed:
        raise SmokeFailure(f"{len(failed)} of {N_REQUESTS} requests "
                           f"failed, first: {failed[0]!r}")
    st = server.stats()
    log(f"served {N_REQUESTS} requests open-loop at {RATE_QPS} q/s in "
        f"{wall:.1f} s; batches {st['rungs']}")

    ref = Retriever(dataclasses.replace(cfg, scan_impl="jnp"))
    t0 = time.perf_counter()
    ref_s, ref_i = jax.jit(lambda s, q: ref.search(s, q, k=top_k))(
        state, queries)
    ref_s, ref_i = np.asarray(ref_s), np.asarray(ref_i)
    log(f"jnp reference search of {N_QUERIES} queries "
        f"{time.perf_counter() - t0:.1f} s (compile included)")
    rows = [j % N_QUERIES for j in range(N_REQUESTS)]
    agreement = check_against(
        np.stack([np.asarray(x[0]) for x in results]),
        np.stack([np.asarray(x[1]) for x in results]),
        ref_s[rows], ref_i[rows], "served results vs the jnp reference")
    hits = np.mean([t in np.asarray(results[j][1])
                    for j, t in enumerate(targets)])
    log(f"reference agreement: {agreement}")
    log(f"source page in the top {top_k} for {hits:.3f} of queries")
    if hits < MIN_SOURCE_HITS:
        raise SmokeFailure(f"the query's source page is in the top {top_k} "
                           f"for only {hits:.3f} of queries")
    log(f"peak_bytes_in_use "
        f"{(dev.memory_stats() or {}).get('peak_bytes_in_use')}")
    return dev


def four_chips(seed: int):
    import jax
    import numpy as np

    from repro.configs.colpali_hpc import COLPALI_HPC
    from repro.data import synthetic
    from repro.launch import serve
    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.retrieval import Corpus, Query, Retriever

    devs = tpu_devices(4)
    log(cache_note(enable_compile_cache()))
    mesh = make_mesh((4,), ("data",), devices=devs)
    arch = COLPALI_HPC.config
    top_k = arch.top_k
    # full-batch Lloyd: the sharded trainer's algorithm, so the one-device
    # build it is compared with trains the same codebook
    r = Retriever(dataclasses.replace(arch.hpc, kmeans_minibatch=0))
    spec = serve.page_spec(arch)
    k_bank, k_pages, k_query, k_build = jax.random.split(
        jax.random.PRNGKey(seed), 4)
    banks = synthetic.make_topic_banks(k_bank, spec)
    pages = synthetic.make_page_chunk(k_pages, banks, spec, FOUR_CHIP_PAGES)
    targets = np.arange(N_QUERIES) * (FOUR_CHIP_PAGES // N_QUERIES)
    queries = Query(*synthetic.make_page_queries(
        k_query, pages[0], jax.numpy.asarray(targets), spec))
    corpus = Corpus(*pages)

    t0 = time.perf_counter()
    local = r.build(k_build, corpus)
    jax.block_until_ready(local)
    log(f"one-device build {time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    sharded = r.shard(r.build(k_build, corpus, mesh=mesh), mesh)
    jax.block_until_ready(sharded)
    log(f"sharded build + shard {time.perf_counter() - t0:.1f} s")
    del corpus, pages

    codes = sharded.backend_state.codes
    shards = codes.addressable_shards
    quarter = (FOUR_CHIP_PAGES // 4,) + codes.shape[1:]
    if (len({s.device for s in shards}) != 4
            or any(s.data.shape != quarter for s in shards)):
        raise SmokeFailure(f"codes not split in quarters: "
                           f"{[(s.device, s.data.shape) for s in shards]}")
    log(f"codes {codes.shape} split over 4 devices, {quarter} each")
    # reported, not asserted: the sharded trainer is the one-device
    # algorithm up to the psum's reassociation, which Lloyd's iterations
    # amplify; the search check below is on one and the same index
    cb_diff = float(np.max(np.abs(np.asarray(local.codebook)
                                  - np.asarray(sharded.codebook))))
    same_codes = float(np.mean(np.asarray(local.backend_state.codes)
                               == np.asarray(codes)))
    log(f"sharded vs one-device build: codebook max |diff| {cb_diff:.3g}, "
        f"identical codes {same_codes:.4f}")

    def search(s, q):
        return r.search(s, q, k=top_k)

    fn = jax.jit(lambda s, q: r.search_sharded(s, q, k=top_k, mesh=mesh))
    if "tpu_custom_call" not in fn.lower(sharded, queries).as_text():
        raise SmokeFailure("no Pallas kernel in the sharded search")
    s_m, i_m = fn(sharded, queries)
    s_1, i_1 = jax.jit(search)(jax.device_put(sharded, devs[0]), queries)
    log("search_sharded vs search of the same index on one device: "
        + check_against(s_m, i_m, s_1, i_1, "sharded vs one-device search"))
    s_l, i_l = jax.jit(search)(local, queries)
    log(f"vs the one-device build: ids identical at "
        f"{float(np.mean(np.asarray(i_m) == np.asarray(i_l))):.4f} of ranks")
    return devs[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        dev = (four_chips if args.chips == 4 else one_chip)(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    import jax
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
