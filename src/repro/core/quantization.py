"""K-Means quantization of patch embeddings (HPC-ColPali §III-B).

Replaces D-dim float32 patch embeddings with 1-byte centroid indices
(K <= 256) or 2-byte indices (K <= 65536), giving up to 32x storage
compression for D=128/float32.

TPU adaptation (docs/design.md §2): FAISS's CPU Lloyd iteration is replaced by a
fully batched, jit-compiled Lloyd step where

  * assignment is one MXU matmul:  argmin_k ||x||^2 - 2 x C^T + ||c_k||^2
  * the centroid update is a ``segment_sum`` scatter,

plus k-means++ seeding via distance-weighted categorical sampling.

Codebook training v2 adds the quality machinery that closes the seed
retrieval gap (ISSUE 3):

  * multi-restart fitting — ``n_restarts`` independent seeds refined under
    ``lax.map`` (sequential, memory-bounded), the lowest-inertia restart
    wins;
  * empty-cluster repair — every Lloyd step re-seeds zero-count centroids
    on the points farthest from their assigned centroid, instead of
    leaving dead centroids frozen at their stale position;
  * best-iterate tracking — Lloyd with repair is not monotone, so the fit
    returns the *lowest-inertia* iterate seen, never just the last one;
  * full-data k-means++ seeding (``seed_batch=0``) when the subsample
    would be the quality bottleneck, and a mini-batch Lloyd mode
    (``minibatch=b``) for corpora too large for full-batch E-steps.

Everything is functional and mesh-shardable: points shard over the data
axes, the codebook is replicated, and per-cluster sums reduce with
``psum`` when run under ``shard_map`` (see core/distributed.py, which
reuses ``pairwise_sq_dists``/``_repair_dead_centroids`` so the sharded
and single-host paths agree within float tolerance). With the default
config the single-host fit is bit-stable: a pure function of
``(key, x, config)`` with no device-dependent branches.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class KMeansConfig:
    """Configuration for codebook training."""

    k: int = 256            # number of centroids (paper: 128 / 256 / 512)
    iters: int = 25         # Lloyd iterations
    seed_batch: int = 4096  # k-means++ seeding subsample; 0 = seed on all
                            # of x (quality over O(seed_batch * K) cost)
    n_restarts: int = 8     # independent fits; lowest final inertia wins
    minibatch: int = 0      # 0 = full-batch Lloyd; else per-step sample
                            # size (Sculley-style streaming update)
    dtype: jnp.dtype = jnp.float32

    @property
    def bits(self) -> int:
        """b = ceil(log2 K) — bits per code in binary mode (paper §III-D)."""
        return max(1, int(jnp.ceil(jnp.log2(self.k))))

    @property
    def code_dtype(self) -> jnp.dtype:
        return jnp.uint8 if self.k <= 256 else jnp.uint16


def pairwise_sq_dists(x: Array, c: Array) -> Array:
    """||x_i - c_k||^2 for x (N, D), c (K, D) -> (N, K). One MXU matmul.

    Clamped at zero: the matmul form cancels catastrophically when x_i is
    (nearly) a centroid, and small *negative* squared distances poison
    every downstream consumer that treats the output as a distance — the
    k-means++ categorical weights (log of a negative) and inertia /
    ``quantization_error`` sums. Argmin is unaffected by the clamp.

    The matmul runs at full f32 precision, as the Pallas assignment kernel
    does: at the default precision the TPU rounds x and c to bf16, which
    moves near-tie assignments and lets the sharded and single-device
    k-means drift apart.
    """
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)          # (N, 1)
    c2 = jnp.sum(c * c, axis=-1)                         # (K,)
    xc = jnp.matmul(x, c.T,
                    precision=jax.lax.Precision.HIGHEST)  # (N, K) — MXU
    return jnp.maximum(x2 - 2.0 * xc + c2[None, :], 0.0)


def assign(x: Array, centroids: Array) -> Array:
    """Nearest-centroid assignment -> integer codes (N,).

    The Pallas-accelerated version lives in kernels/kmeans_assign.py; this is
    the canonical jnp form used for training the codebook and as oracle.
    """
    return jnp.argmin(pairwise_sq_dists(x, centroids), axis=-1)


def decode(codes: Array, centroids: Array) -> Array:
    """codes (…,) -> reconstructed embeddings (…, D) by centroid gather."""
    return jnp.take(centroids, codes.astype(jnp.int32), axis=0)


def _kmeans_pp_init(key: Array, x: Array, k: int) -> Array:
    """k-means++ seeding on a (N, D) sample, fully inside lax.scan/fori."""
    n = x.shape[0]
    k0, key = jax.random.split(key)
    first = jax.random.randint(k0, (), 0, n)
    centroids0 = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(x[first])
    d2_0 = pairwise_sq_dists(x, x[first][None])[:, 0]

    def body(i, carry):
        centroids, d2, key = carry
        key, sub = jax.random.split(key)
        # Sample next seed proportionally to squared distance (k-means++).
        logits = jnp.log(jnp.maximum(d2, 1e-30))
        idx = jax.random.categorical(sub, logits)
        c_new = x[idx]
        centroids = centroids.at[i].set(c_new)
        d2 = jnp.minimum(d2, pairwise_sq_dists(x, c_new[None])[:, 0])
        return centroids, d2, key

    centroids, _, _ = jax.lax.fori_loop(1, k, body, (centroids0, d2_0, key))
    return centroids


def _repair_dead_centroids(x: Array, centroids: Array, counts: Array,
                           min_d2: Array) -> Array:
    """Re-seed zero-count centroids on the farthest points.

    The r-th dead centroid (in index order) moves to the point with the
    r-th largest distance-to-assigned-centroid, so repaired centroids land
    where the codebook underfits instead of staying frozen. Shapes:
    x (N, D), centroids (K, D), counts (K,), min_d2 (N,).
    """
    k = centroids.shape[0]
    kk = min(k, x.shape[0])
    # JAX04-safe: kk = min(k, N) just above
    _, far_idx = jax.lax.top_k(min_d2, kk)  # noqa: JAX04 - farthest points
    dead = counts <= 0
    rank = jnp.clip(jnp.cumsum(dead.astype(jnp.int32)) - 1, 0, kk - 1)
    repl = x[far_idx[rank]]
    return jnp.where(dead[:, None], repl, centroids)


def _lloyd_step(x: Array, centroids: Array) -> Tuple[Array, Array]:
    """One Lloyd iteration with empty-cluster repair.

    Returns (new_centroids, inertia) where inertia is the mean squared
    distance of x to the *input* centroids (the quantity Lloyd descends).
    """
    k = centroids.shape[0]
    d2 = pairwise_sq_dists(x, centroids)
    codes = jnp.argmin(d2, axis=-1)
    min_d2 = jnp.min(d2, axis=-1)
    inertia = jnp.mean(min_d2)
    # Scatter-reduce: per-cluster sums and counts.
    sums = jax.ops.segment_sum(x, codes, num_segments=k)
    counts = jax.ops.segment_sum(jnp.ones((x.shape[0],), x.dtype), codes,
                                 num_segments=k)
    new_centroids = jnp.where(counts[:, None] > 0,
                              sums / jnp.maximum(counts[:, None], 1.0),
                              centroids)
    new_centroids = _repair_dead_centroids(x, new_centroids, counts, min_d2)
    return new_centroids, inertia


def _inertia(x: Array, centroids: Array) -> Array:
    """Mean squared distance of x to its nearest centroid."""
    return jnp.mean(jnp.min(pairwise_sq_dists(x, centroids), axis=-1))


def kmeans_refine(x: Array, centroids0: Array, iters: int
                  ) -> Tuple[Array, Array, Array]:
    """Run `iters` Lloyd steps from `centroids0`, tracking the best iterate.

    Lloyd with empty-cluster repair is not monotone in inertia, so the
    returned codebook is the lowest-inertia iterate seen (including the
    final one), not whatever the last step produced.

    Returns (best_centroids, per-iteration inertia (iters,), best_inertia).
    """
    init = (centroids0, centroids0, jnp.asarray(jnp.inf, x.dtype))

    def body(carry, _):
        c, best_c, best_i = carry
        new_c, inertia = _lloyd_step(x, c)
        better = inertia < best_i
        best_c = jnp.where(better, c, best_c)
        best_i = jnp.where(better, inertia, best_i)
        return (new_c, best_c, best_i), inertia

    (c_last, best_c, best_i), inertias = jax.lax.scan(
        body, init, None, length=iters)
    last_i = _inertia(x, c_last)
    better = last_i < best_i
    best_c = jnp.where(better, c_last, best_c)
    best_i = jnp.where(better, last_i, best_i)
    return best_c, inertias, best_i


def _minibatch_refine(key: Array, x: Array, centroids0: Array, iters: int,
                      batch: int) -> Tuple[Array, Array]:
    """Mini-batch Lloyd (Sculley): per-step sample, cumulative-count step.

    Each centroid moves toward its batch mean with learning rate
    n_batch / n_cumulative, so early batches move centroids fast and the
    trajectory converges as counts accumulate. Centroids that have never
    received a point are re-seeded on the batch's farthest points.
    """
    n = x.shape[0]
    k = centroids0.shape[0]
    keys = jax.random.split(key, iters)

    def body(carry, kt):
        c, cum = carry
        # with replacement (standard Sculley): O(batch) per step, where
        # replace=False sampling would cost O(n) work/memory every step
        idx = jax.random.randint(kt, (batch,), 0, n)
        xb = x[idx]
        d2 = pairwise_sq_dists(xb, c)
        codes = jnp.argmin(d2, axis=-1)
        min_d2 = jnp.min(d2, axis=-1)
        sums = jax.ops.segment_sum(xb, codes, num_segments=k)
        cnts = jax.ops.segment_sum(jnp.ones((batch,), x.dtype), codes,
                                   num_segments=k)
        cum_new = cum + cnts
        target = sums / jnp.maximum(cnts[:, None], 1.0)
        eta = (cnts / jnp.maximum(cum_new, 1.0))[:, None]
        new_c = jnp.where(cnts[:, None] > 0, c + eta * (target - c), c)
        new_c = _repair_dead_centroids(xb, new_c, cum_new, min_d2)
        return (new_c, cum_new), jnp.mean(min_d2)

    (c, _), inertias = jax.lax.scan(
        body, (centroids0, jnp.zeros((k,), x.dtype)), keys)
    return c, inertias


def seed_centroids(k_seed: Array, k_init: Array, x: Array,
                   config: KMeansConfig) -> Array:
    """k-means++ seeds for one restart (shared by the sharded trainer).

    Seeds on a `seed_batch` subsample (or all of x when `seed_batch=0` or
    x is smaller), sampled explicitly WITHOUT replacement — sampling with
    replacement would seed duplicate points (v0's `replace=n < m` guard
    was dead code: m = min(seed_batch, n) makes it always False).
    """
    n = x.shape[0]
    m = config.seed_batch if config.seed_batch > 0 else n
    m = min(m, n)
    if m < n:
        sel = jax.random.choice(k_seed, n, (m,), replace=False)
        seed_x = x[sel]
    else:
        seed_x = x
    return _kmeans_pp_init(k_init, seed_x, config.k)


def _fit_single(key: Array, x: Array, config: KMeansConfig,
                eval_idx: Array = None) -> Tuple[Array, Array, Array]:
    """One seeded fit -> (centroids, per-iter inertia, final inertia)."""
    n = x.shape[0]
    k_seed, k_init, k_mb = jax.random.split(key, 3)
    centroids0 = seed_centroids(k_seed, k_init, x, config)
    if config.minibatch and config.minibatch < n:
        c, inertias = _minibatch_refine(k_mb, x, centroids0, config.iters,
                                        config.minibatch)
        # Restart selection needs a final-inertia estimate, but the full
        # (N, K) E-step is exactly what mini-batch mode exists to avoid:
        # estimate on one eval batch instead. kmeans_fit passes the SAME
        # eval_idx to every restart so selection compares like with like
        # (per-restart eval batches would add selection noise).
        if eval_idx is None:                       # standalone call
            k_eval = jax.random.fold_in(k_mb, config.iters)
            eval_idx = jax.random.randint(k_eval, (config.minibatch,), 0, n)
        return c, inertias, _inertia(x[eval_idx], c)
    best_c, inertias, best_i = kmeans_refine(x, centroids0, config.iters)
    return best_c, inertias, best_i


@partial(jax.jit, static_argnames=("config",))
def kmeans_fit(key: Array, x: Array, config: KMeansConfig) -> Tuple[Array, Array]:
    """Train a K-Means codebook on patch embeddings x (N, D).

    Runs `config.n_restarts` independent seeded fits (sequentially under
    `lax.map`, so peak memory stays one restart's worth) and returns the
    restart with the lowest final inertia.

    Returns (centroids (K, D), per-iteration inertia (iters,)).
    """
    x = x.astype(config.dtype)
    n = x.shape[0]
    restarts = max(1, config.n_restarts)
    if config.minibatch and config.minibatch < n:
        # one eval batch shared by every restart (see _fit_single); the
        # key split happens only in mini-batch mode so the full-batch
        # path keeps its bit-stable key derivation
        key, k_eval = jax.random.split(key)
        eval_idx = jax.random.randint(k_eval, (config.minibatch,), 0, n)
    else:
        eval_idx = None
    keys = jax.random.split(key, restarts)
    cents, inertias, final = jax.lax.map(
        lambda kk: _fit_single(kk, x, config, eval_idx), keys)
    best = jnp.argmin(final)
    return cents[best], inertias[best]


def quantize(x: Array, centroids: Array, code_dtype=jnp.uint8, *,
             impl: str = "jnp") -> Array:
    """Quantize embeddings (…, M, D) -> codes (…, M) of code_dtype.

    Works for arbitrary leading batch dims (vmapped assignment). `impl`
    routes the assignment: the default "jnp" is the canonical form;
    "auto" uses the Pallas kernel on TPU and the canonical form elsewhere
    (what every index build passes — on the chip the canonical form
    would materialise an (N, K) distance matrix); anything else is
    forwarded to `repro.kernels.ops.kmeans_assign`
    ("pallas"/"interpret"/"ref").
    """
    flat = x.reshape(-1, x.shape[-1])
    if impl == "auto" and jax.default_backend() != "tpu":
        impl = "jnp"
    if impl == "jnp":
        codes = assign(flat, centroids)
    else:
        from repro.kernels import ops as kernel_ops  # lazy: avoid cycle
        codes = kernel_ops.kmeans_assign(flat, centroids, impl=impl)
    return codes.astype(code_dtype).reshape(x.shape[:-1])


def quantization_error(x: Array, centroids: Array) -> Array:
    """Mean squared reconstruction error of the codebook on x (N, D).

    Exactly the k-means inertia: the (clamped, hence non-negative) squared
    distance to the nearest centroid.
    """
    return _inertia(x, centroids)


# ---------------------------------------------------------------------------
# Product-quantization extension (paper §VII "Future work"): split D into
# n_sub sub-spaces with an independent codebook each. Kept API-compatible
# with the single-codebook path; used by benchmarks/storage.py ablations.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PQConfig:
    k: int = 256
    n_sub: int = 4
    iters: int = 15
    seed_batch: int = 4096
    n_restarts: int = 8
    minibatch: int = 0


@partial(jax.jit, static_argnames=("config",))
def pq_fit(key: Array, x: Array, config: PQConfig) -> Array:
    """Train per-subspace codebooks -> (n_sub, K, D/n_sub)."""
    n, d = x.shape
    assert d % config.n_sub == 0, "D must divide n_sub"
    ds = d // config.n_sub
    sub = x.reshape(n, config.n_sub, ds).transpose(1, 0, 2)  # (n_sub, N, ds)
    keys = jax.random.split(key, config.n_sub)
    kcfg = KMeansConfig(k=config.k, iters=config.iters,
                        seed_batch=config.seed_batch,
                        n_restarts=config.n_restarts,
                        minibatch=config.minibatch)
    fit = lambda kk, xx: kmeans_fit(kk, xx, kcfg)[0]
    return jax.vmap(fit)(keys, sub)


def pq_quantize(x: Array, codebooks: Array) -> Array:
    """x (…, D) -> codes (…, n_sub) uint8/16."""
    n_sub, k, ds = codebooks.shape
    flat = x.reshape(-1, n_sub, ds).transpose(1, 0, 2)       # (n_sub, N, ds)
    codes = jax.vmap(assign)(flat, codebooks)                # (n_sub, N)
    dt = jnp.uint8 if k <= 256 else jnp.uint16
    return codes.T.reshape(*x.shape[:-1], n_sub).astype(dt)


def pq_decode(codes: Array, codebooks: Array) -> Array:
    """codes (…, n_sub) -> x̂ (…, n_sub*ds)."""
    n_sub, _, ds = codebooks.shape
    flat = codes.reshape(-1, n_sub).astype(jnp.int32)        # (N, n_sub)
    parts = jax.vmap(lambda cb, c: cb[c], in_axes=(0, 1))(codebooks, flat)
    return parts.transpose(1, 0, 2).reshape(*codes.shape[:-1], n_sub * ds)
