"""Mesh-sharded index building: the paper's system at production scale.

The search over a corpus sharded across the mesh is
`Retriever.search_sharded` (each device scans its own pages, the (B, k)
candidates all-gather into a global top-k). This module holds what the
sharded build needs: the K-Means v2 trainer with points sharded over
devices, a replicated codebook, per-cluster sums reduced with psum,
empty-cluster repair via a local-top-k/all-gather/global-top-k
farthest-point merge, and multi-restart select-best — the same algorithm
as the single-host `quantization.kmeans_fit` (seeding reuses its
`seed_centroids`, so on a 1-device mesh the two paths agree within float
tolerance). This is the streaming-codebook building block the paper lists
as future work (§VII), wired into `Retriever.build(..., mesh=...)` via
`sharded_kmeans_fit` / `sharded_quantize`.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import quantization as quant
from repro.dist.sharding import Sharder

Array = jax.Array


def corpus_data_axes(mesh: Mesh, n: int) -> Tuple[str, ...]:
    """Mesh axes an N-point dimension shards over on this mesh.

    Resolved through the logical-axis Sharder's "corpus" rule
    (dist/sharding.py DEFAULT_RULES — one source of truth with
    `Retriever.shard`, so build-time and search-time sharding can't
    drift): missing axes are skipped and axes drop from the right until n
    divides the shard product. Returns () when nothing divides (the
    sharded build stages then raise).
    """
    entry = Sharder(mesh).resolve(("corpus",), (n,))[0]
    if entry is None:
        return ()
    return (entry,) if isinstance(entry, str) else tuple(entry)


def sharded_kmeans_refine_fn(mesh: Mesh, data_axes: Tuple[str, ...], *,
                             k: int, iters: int, n_total: int,
                             block_rows: int = 65536):
    """Distributed Lloyd v2: x sharded over data_axes, codebook replicated.

    Each step: local assignment (matmul, streamed in `block_rows` row
    blocks so the per-device transient is (block_rows, K), never
    (N_local, K)) -> local segment sums -> psum over the data axes ->
    replicated centroid update -> empty-cluster repair (each device's
    top-k farthest points are all-gathered and re-top-k'd, so dead
    centroids re-seed on the *global* farthest points — same rule as
    quantization._repair_dead_centroids). Tracks the lowest-inertia
    iterate exactly like quantization.kmeans_refine. Row blocking is
    bitwise-transparent: every row's argmin/min is independent of the
    chunking.

    Returns f(x, centroids0) -> (best_centroids, inertias (iters,),
    best_inertia) with x sharded over data_axes and everything else
    replicated.
    """
    x_spec = P(data_axes)
    n_f = float(n_total)

    def psum_all(v):
        for ax in data_axes:
            v = jax.lax.psum(v, ax)
        return v

    def e_step(x, centroids):
        n = x.shape[0]
        if n <= block_rows:
            d2 = quant.pairwise_sq_dists(x, centroids)
            return jnp.argmin(d2, axis=-1), jnp.min(d2, axis=-1)
        nb = -(-n // block_rows)
        xp = jnp.pad(x, ((0, nb * block_rows - n), (0, 0)))

        def block(xb):
            d2 = quant.pairwise_sq_dists(xb, centroids)
            return jnp.argmin(d2, axis=-1), jnp.min(d2, axis=-1)

        codes, min_d2 = jax.lax.map(block, xp.reshape(nb, block_rows, -1))
        return codes.reshape(-1)[:n], min_d2.reshape(-1)[:n]

    def repair(x, centroids, cnts, min_d2):
        kk = min(k, x.shape[0])
        # JAX04-safe: kk = min(k, shard size) just above
        far_d, far_i = jax.lax.top_k(min_d2, kk)  # noqa: JAX04
        far_x = x[far_i]                                   # (kk, D)
        for ax in data_axes:
            far_d = jax.lax.all_gather(far_d, ax, axis=0, tiled=True)
            far_x = jax.lax.all_gather(far_x, ax, axis=0, tiled=True)
        # JAX04-safe: k clamped to the gathered axis length inline
        g_d, g_pos = jax.lax.top_k(far_d, min(k, far_d.shape[0]))  # noqa: JAX04
        cand = far_x[g_pos]                                # global farthest
        dead = cnts <= 0
        rank = jnp.clip(jnp.cumsum(dead.astype(jnp.int32)) - 1, 0,
                        cand.shape[0] - 1)
        return jnp.where(dead[:, None], cand[rank], centroids)

    def fit(x, centroids0):
        def step(carry, _):
            c, best_c, best_i = carry
            codes, min_d2 = e_step(x, c)
            inertia = psum_all(jnp.sum(min_d2)) / n_f
            sums = psum_all(jax.ops.segment_sum(x, codes, num_segments=k))
            cnts = psum_all(jax.ops.segment_sum(
                jnp.ones((x.shape[0],), x.dtype), codes, num_segments=k))
            new_c = jnp.where(cnts[:, None] > 0,
                              sums / jnp.maximum(cnts[:, None], 1.0), c)
            new_c = repair(x, new_c, cnts, min_d2)
            better = inertia < best_i
            best_c = jnp.where(better, c, best_c)
            best_i = jnp.where(better, inertia, best_i)
            return (new_c, best_c, best_i), inertia

        init = (centroids0, centroids0, jnp.asarray(jnp.inf, x.dtype))
        (c_last, best_c, best_i), inertias = jax.lax.scan(
            step, init, None, length=iters)
        _, min_d2 = e_step(x, c_last)
        last_i = psum_all(jnp.sum(min_d2)) / n_f
        better = last_i < best_i
        best_c = jnp.where(better, c_last, best_c)
        best_i = jnp.where(better, last_i, best_i)
        return best_c, inertias, best_i

    return jax.jit(jax.shard_map(
        fit, mesh=mesh, in_specs=(x_spec, P()), out_specs=(P(), P(), P()),
        check_vma=False))


def sharded_kmeans_fit(mesh: Mesh, key: Array, x: Array,
                       config: quant.KMeansConfig,
                       data_axes: Optional[Tuple[str, ...]] = None
                       ) -> Tuple[Array, Array]:
    """Mesh-sharded `quantization.kmeans_fit`: same seeds, same algorithm.

    Per restart: k-means++ seeding on the (replicated, O(seed_batch))
    subsample using the exact keys the single-host path derives, then the
    shard_map'd Lloyd v2 over x sharded on `data_axes`; the restart with
    the lowest final inertia wins. Raises `ValueError` when the mesh has
    none of the ("pod", "data", "model") corpus axes or N doesn't divide
    the shard product: a one-device fallback would silently bring back
    the full-device-memory build the mesh was meant to avoid.

    Stochastic mini-batch mode is single-host-only; here `config.minibatch`
    instead bounds the E-step's per-device transient to
    (minibatch, K) row blocks (streamed full-batch — bitwise identical to
    the unblocked E-step), so corpus-scale N never materialises an
    (N_local, K) distance matrix.

    Returns (centroids (K, D), per-iteration inertia (iters,)) like
    `kmeans_fit`; on a 1-device mesh the result matches the single-host
    path within float tolerance (psum reassociates the per-cluster sums).
    """
    x = x.astype(config.dtype)
    n = x.shape[0]
    if data_axes is None:
        data_axes = corpus_data_axes(mesh, n)
    if not data_axes:
        raise ValueError(
            f"sharded_kmeans_fit: no 'corpus'-rule mesh axis divides "
            f"N={n} on mesh "
            f"{dict(zip(mesh.axis_names, mesh.devices.shape))}; a sharded "
            "build cannot run on one device — pad the corpus to a multiple "
            "of the shard count or build without a mesh")
    refine = sharded_kmeans_refine_fn(
        mesh, data_axes, k=config.k, iters=config.iters, n_total=n,
        block_rows=config.minibatch if config.minibatch > 0 else 65536)
    x_sh = jax.device_put(x, NamedSharding(mesh, P(data_axes)))
    restarts = max(1, config.n_restarts)
    keys = jax.random.split(key, restarts)
    best = None
    for r in range(restarts):
        k_seed, k_init, _ = jax.random.split(keys[r], 3)
        c0 = quant.seed_centroids(k_seed, k_init, x, config)
        c, hist, inertia = refine(x_sh, c0)
        if best is None or float(inertia) < best[0]:
            best = (float(inertia), c, hist)
    return best[1], best[2]


def sharded_quantize(mesh: Mesh, x: Array, codebook: Array, code_dtype,
                     data_axes: Optional[Tuple[str, ...]] = None) -> Array:
    """Quantize (N, ..., D) across the mesh: N sharded, codebook replicated.

    Assignment inside the shard runs through `quantization.quantize`, which
    routes to the Pallas kernel (kernels/kmeans_assign.py) on TPU and the
    reference jnp path elsewhere. Raises `ValueError` when no corpus axis
    divides N.
    """
    n = x.shape[0]
    if data_axes is None:
        data_axes = corpus_data_axes(mesh, n)
    if not data_axes:
        raise ValueError(
            f"sharded_quantize: no corpus mesh axis divides N={n} on mesh "
            f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")
    in_spec = P(*((data_axes,) + (None,) * (x.ndim - 1)))
    out_spec = P(*((data_axes,) + (None,) * (x.ndim - 2)))

    def f(x_local, cb):
        # "auto": Pallas assignment on TPU devices, canonical jnp elsewhere
        return quant.quantize(x_local, cb, code_dtype=code_dtype,
                              impl="auto")

    fn = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(in_spec, P()),
                           out_specs=out_spec, check_vma=False))
    x_sh = jax.device_put(x, NamedSharding(mesh, in_spec))
    return fn(x_sh, codebook)
