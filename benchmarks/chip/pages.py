"""Seeded synthetic pages and queries, made on the device.

A copy of the page model the program ships (`repro.data.synthetic`:
`make_topic_banks`, `make_page_chunk`, `make_page_queries`), kept here so
that a change to the program's generator cannot move the yardstick. A
page holds its own 8 salient and 8 background prototypes, drawn from the
whole bank, so pages do not share one ADC score by the thousand.

Chunk `c` of a corpus is a pure function of (seed, c): the build and the
reference regenerate the same pages chunk by chunk, and the float corpus
(512 KB a page at ColPali's widths) is never whole on the device.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class PageSpec:
    n_patches: int          # patches per page (ColPali: 32 x 32 grid)
    n_q_patches: int        # query tokens
    dim: int                # embedding width D
    n_topics: int
    patches_per_topic: int
    noise: float
    salient_frac: float
    page_protos: int


def spec_from(config: dict) -> PageSpec:
    enc, pages = config["encoder"], config["pages"]
    return PageSpec(n_patches=enc["n_patches"], n_q_patches=enc["query_len"],
                    dim=enc["proj_dim"], **pages)


def corpus_keys(seed: int):
    """(bank key, build key, chunk key, query key) of a run's seed."""
    return jax.random.split(jax.random.PRNGKey(seed), 4)


def make_topic_banks(key, spec: PageSpec):
    """Patch prototypes (n_topics, patches_per_topic, D)."""
    k_c, k_p = jax.random.split(key)
    centers = jax.random.normal(k_c, (spec.n_topics, spec.dim))
    return centers[:, None, :] + 0.7 * jax.random.normal(
        k_p, (spec.n_topics, spec.patches_per_topic, spec.dim))


@partial(jax.jit, static_argnames=("spec", "n"))
def make_page_chunk(key, banks, spec: PageSpec, n: int):
    """n pages: (patches (n, Md, D) f32 unit-norm, mask (n, Md) bool,
    salience (n, Md) f32)."""
    md, d = spec.n_patches, spec.dim
    t, p, s = spec.n_topics, spec.patches_per_topic, spec.page_protos
    n_sal = max(1, int(md * spec.salient_frac))
    ks = jax.random.split(key, 7)
    own_sal = jax.random.randint(ks[0], (n, s), 0, t * p)
    own_bg = jax.random.randint(ks[1], (n, s), 0, t * p)
    proto = jnp.concatenate([
        jnp.take_along_axis(
            own_sal, jax.random.randint(ks[2], (n, n_sal), 0, s), axis=1),
        jnp.take_along_axis(
            own_bg, jax.random.randint(ks[3], (n, md - n_sal), 0, s),
            axis=1)], axis=1)                              # (n, Md)
    patches = banks.reshape(t * p, d)[proto]
    patches = patches + spec.noise * jax.random.normal(ks[4], patches.shape)
    patches = patches / jnp.linalg.norm(patches, axis=-1, keepdims=True)
    sal = jnp.concatenate([
        0.8 + 0.2 * jax.random.uniform(ks[5], (n, n_sal)),
        0.2 * jax.random.uniform(ks[6], (n, md - n_sal))], axis=1)
    return patches, jnp.ones((n, md), bool), sal


@partial(jax.jit, static_argnames=("spec",))
def make_page_queries(key, patches, targets, spec: PageSpec):
    """Queries from the salient patches of rows `targets` of `patches`,
    plus noise: (embeddings (Q, Mq, D), mask (Q, Mq) bool, salience)."""
    n_sal = max(1, int(spec.n_patches * spec.salient_frac))
    q, mq = targets.shape[0], spec.n_q_patches
    k_pick, k_noise, k_sal = jax.random.split(key, 3)
    pick = jax.random.randint(k_pick, (q, mq), 0, n_sal)
    emb = patches[targets[:, None], pick]
    emb = emb + spec.noise * jax.random.normal(k_noise, emb.shape)
    emb = emb / jnp.linalg.norm(emb, axis=-1, keepdims=True)
    sal = 0.5 + 0.5 * jax.random.uniform(k_sal, (q, mq))
    return emb, jnp.ones((q, mq), bool), sal


def chunk_sizes(n_pages: int, chunk: int):
    """Sizes of the chunks a corpus of n_pages is made in."""
    full, tail = divmod(n_pages, chunk)
    return [chunk] * full + ([tail] if tail else [])


def chunk_pages(seed: int, spec: PageSpec, banks, c: int, size: int):
    """Chunk c of the corpus of `seed`."""
    _, _, k_chunks, _ = corpus_keys(seed)
    return make_page_chunk(jax.random.fold_in(k_chunks, c), banks, spec,
                           size)


def chunk_queries(seed: int, spec: PageSpec, pages, c: int, offset: int,
                  n: int):
    """n queries made from pages of chunk c (at global offset `offset`),
    each from its own page. Returns (embeddings, mask, salience, source
    page ids)."""
    _, _, _, k_query = corpus_keys(seed)
    k = jax.random.fold_in(k_query, c)
    size = pages[0].shape[0]
    local = jax.random.choice(jax.random.fold_in(k, 1), size, (n,),
                              replace=False)
    emb, mask, sal = make_page_queries(k, pages[0], local, spec)
    return emb, mask, sal, local + offset
