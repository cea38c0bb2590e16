"""Plain late-interaction scoring over regenerated pages, for the
references under `references/`.

Reads only the seeded pages (made again chunk by chunk by `pages.py`),
the codebook and the configuration: not the index's arrays. The
codebook, which the program fits, is judged on its own
(`codebook_excess`) against a plain k-means of the same pages. A page's
score for a query is the sum over query tokens of the best table entry
among the codes the page holds, which is MaxSim over its patches' codes
whatever the layout that holds them.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import pages as pages_mod

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 256       # pages scored per step


def nearest(x, codebook):
    """Index of the nearest centroid (squared Euclidean) of each row."""
    d2 = (jnp.sum(x * x, -1, keepdims=True)
          - 2.0 * jnp.matmul(x, codebook.T, precision=HIGHEST)
          + jnp.sum(codebook * codebook, -1))
    return jnp.argmin(d2, axis=-1).astype(jnp.int32)


def keep_count(m: int, p: float) -> int:
    return max(1, min(m, int(math.ceil(m * p / 100.0))))


def _present(codes, k):
    """(n, M) codes -> (n, K) bool: which codes each page holds."""
    rows = jnp.arange(codes.shape[0])[:, None]
    return jnp.zeros((codes.shape[0], k), bool).at[rows, codes].set(True)


def _maxsim(table, q_mask, present):
    """table (Q, Mq, K), present (n, K) -> (Q, n) summed best entries."""
    fill = (jnp.iinfo(table.dtype).min
            if jnp.issubdtype(table.dtype, jnp.integer) else -jnp.inf)
    best = jnp.max(jnp.where(present[None, None], table[:, :, None, :],
                             fill), axis=-1)                # (Q, Mq, n)
    return jnp.sum(jnp.where(q_mask[:, :, None], best, 0), axis=1)


@partial(jax.jit, static_argnames=("keep", "code_bits"))
def _chunk_scores(patches, mask, salience, codebook, table, q_mask, *,
                  keep: int, code_bits: int):
    """Scores of one chunk's pages: (pruned (Q, n), full (Q, n))."""
    k = table.shape[-1]
    n, m, d = patches.shape
    blocks = n // BLOCK

    def block(args):
        x, mk, sal = args
        codes = nearest(x.reshape(-1, d), codebook).reshape(BLOCK, m)
        codes = codes & ((1 << code_bits) - 1)
        sal = jnp.where(mk, sal, -jnp.inf)
        _, kept = jax.lax.top_k(sal, keep)
        pruned = jnp.take_along_axis(codes, kept, axis=1)
        return (_maxsim(table, q_mask, _present(pruned, k)),
                _maxsim(table, q_mask, _present(codes, k)))

    def split(a):
        return a.reshape((blocks, BLOCK) + a.shape[1:])

    pr, full = jax.lax.map(block, (split(patches), split(mask),
                                   split(salience)))
    q = table.shape[0]
    return (jnp.moveaxis(pr, 0, 1).reshape(q, n),
            jnp.moveaxis(full, 0, 1).reshape(q, n))


FIT_SAMPLE = 65536     # patches the codebooks are fit on
EVAL_SAMPLE = 65536    # other patches every codebook is judged on
LLOYD_ITERS = 30


def _sq_dists(x, c, precision):
    return jnp.maximum(jnp.sum(x * x, -1, keepdims=True)
                       - 2.0 * jnp.matmul(x, c.T, precision=precision)
                       + jnp.sum(c * c, -1), 0.0)


def distortion(x, codebook):
    """Mean squared distance of the rows of x to their nearest centroid."""
    return jnp.mean(jnp.min(_sq_dists(x, codebook, HIGHEST), -1))


def lloyd(x, c, iters: int, precision=HIGHEST):
    """`iters` full-batch Lloyd steps from centroids c; an empty cluster
    keeps its centroid."""
    k, n = c.shape[0], x.shape[0]

    def step(c, _):
        a = jnp.argmin(_sq_dists(x, c, precision), -1)
        sums = jax.ops.segment_sum(x, a, num_segments=k)
        count = jax.ops.segment_sum(jnp.ones(n, x.dtype), a, num_segments=k)
        return jnp.where(count[:, None] > 0,
                         sums / jnp.maximum(count, 1.0)[:, None], c), None

    return jax.lax.scan(step, c, None, length=iters)[0]


@partial(jax.jit, static_argnames=("k", "restarts", "iters", "precision"))
def kmeans(key, x, *, k: int, restarts: int, iters: int,
           precision=HIGHEST):
    """Plain k-means: k-means++ seeds, then `iters` Lloyd steps with
    distances at `precision`; the best of `restarts` fits by distortion
    on x."""
    n, d = x.shape

    def seeds(key):
        k0, key = jax.random.split(key)
        first = x[jax.random.randint(k0, (), 0, n)]
        c = jnp.zeros((k, d), x.dtype).at[0].set(first)
        d2 = jnp.sum((x - first) ** 2, -1)

        def pick(i, carry):
            c, d2, key = carry
            key, sub = jax.random.split(key)
            j = jax.random.categorical(sub, jnp.log(d2 + 1e-30))
            c = c.at[i].set(x[j])
            return c, jnp.minimum(d2, jnp.sum((x - x[j]) ** 2, -1)), key

        return jax.lax.fori_loop(1, k, pick, (c, d2, key))[0]

    def fit(key):
        c = lloyd(x, seeds(key), iters, precision)
        return c, distortion(x, c)

    cs, dist = jax.lax.map(fit, jax.random.split(key, restarts))
    return cs[jnp.argmin(dist)]


# plain k-means fits put in the program's place: weaker than the
# configuration states ("restarts-1", "seeds-only"), or with their
# distances one precision down ("bf16")
CODEBOOK_CONTROLS = {"restarts-1": {"restarts": 1},
                     "seeds-only": {"iters": 0},
                     "bf16": {"precision": jax.lax.Precision.DEFAULT}}


def codebook_excess(config: dict, seed: int, n_pages: int, chunk: int,
                    codebooks: dict, controls=()):
    """How much worse each codebook of `codebooks` quantises the seeded
    pages than a plain k-means with the configuration's K and restarts:
    {name: distortion / the plain fit's distortion - 1}, on patches the
    fits did not see; also for each named fit of CODEBOOK_CONTROLS in
    `controls`. The patches are drawn from the first chunk of pages, the
    one the program fits its codebook on."""
    spec = pages_mod.spec_from(config)
    banks = pages_mod.make_topic_banks(pages_mod.corpus_keys(seed)[0], spec)
    size = pages_mod.chunk_sizes(n_pages, chunk)[0]
    patches = pages_mod.chunk_pages(seed, spec, banks, 0, size)[0]
    flat = patches.reshape(-1, spec.dim)
    k_fit, k_eval, k_km = jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(seed), 5), 3)
    n = flat.shape[0]
    fit_x = flat[jax.random.randint(k_fit, (min(FIT_SAMPLE, n),), 0, n)]
    eval_x = flat[jax.random.randint(k_eval, (min(EVAL_SAMPLE, n),), 0, n)]
    del patches, flat
    plain = {"k": config["hpc"]["k"],
             "restarts": config["hpc"]["kmeans_restarts"],
             "iters": LLOYD_ITERS}
    base = float(distortion(eval_x, kmeans(k_km, fit_x, **plain)))
    fits = dict(codebooks)
    for name in controls:
        fits[name] = kmeans(k_km, fit_x,
                            **dict(plain, **CODEBOOK_CONTROLS[name]))
    return {name: float(distortion(eval_x, jnp.asarray(c))) / base - 1.0
            for name, c in fits.items()}


def corpus_scores(config: dict, seed: int, n_pages: int, chunk: int,
                  codebook, table, q_mask, code_bits: int):
    """(pruned (Q, N), full (Q, N)) scores over the whole seeded corpus,
    as host arrays. Pages are made again chunk by chunk."""
    spec = pages_mod.spec_from(config)
    keep = keep_count(spec.n_patches, config["hpc"]["p"])
    banks = pages_mod.make_topic_banks(pages_mod.corpus_keys(seed)[0], spec)
    codebook, table = jnp.asarray(codebook), jnp.asarray(table)
    q_mask = jnp.asarray(q_mask)
    pruned, full = [], []
    for c, size in enumerate(pages_mod.chunk_sizes(n_pages, chunk)):
        if size % BLOCK:
            raise ValueError(f"chunk of {size} pages is not a multiple of "
                             f"{BLOCK}")
        pg = pages_mod.chunk_pages(seed, spec, banks, c, size)
        p, f = _chunk_scores(*pg, codebook, table, q_mask, keep=keep,
                             code_bits=code_bits)
        pruned.append(np.asarray(p))
        full.append(np.asarray(f))
    return np.concatenate(pruned, axis=1), np.concatenate(full, axis=1)
