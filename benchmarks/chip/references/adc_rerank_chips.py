"""The plain reference of the flat ADC search with rerank (`adc_rerank`),
with its chunk loop spread over every chip of the host, for a corpus of
millions of pages (`colpali-hpc-4chip`).

The same table, the same `refcore` scoring of each chunk of regenerated
pages, the same candidates and limits as `adc_rerank`: chunk c is made
and scored on chip c mod the chip count, and each chunk's scores come to
the host one turn of the chips later, so every chip works while the
host waits for one and none holds more than two chunks of pages.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import pages as pages_mod
from benchmarks.chip import refcore
from benchmarks.chip.references import adc_rerank

LIMITS = adc_rerank.LIMITS
VARIANTS = adc_rerank.VARIANTS


def corpus_scores(config: dict, seed: int, n_pages: int, chunk: int,
                  codebook, table, q_mask, code_bits: int):
    """`refcore.corpus_scores`, chunk c on chip c mod the chip count."""
    spec = pages_mod.spec_from(config)
    keep = refcore.keep_count(spec.n_patches, config["hpc"]["p"])
    banks = pages_mod.make_topic_banks(pages_mod.corpus_keys(seed)[0], spec)
    chips = jax.devices()
    held = [jax.device_put((banks, jnp.asarray(codebook), jnp.asarray(table),
                            jnp.asarray(q_mask)), d) for d in chips]
    pruned, full, queued = [], [], []
    for c, size in enumerate(pages_mod.chunk_sizes(n_pages, chunk)):
        if size % refcore.BLOCK:
            raise ValueError(f"chunk of {size} pages is not a multiple of "
                             f"{refcore.BLOCK}")
        b, cb, tb, qm = held[c % len(chips)]
        with jax.default_device(chips[c % len(chips)]):
            pg = pages_mod.chunk_pages(seed, spec, b, c, size)
        queued.append(refcore._chunk_scores(*pg, cb, tb, qm, keep=keep,
                                            code_bits=code_bits))
        del pg
        if len(queued) > len(chips):
            p, f = queued.pop(0)
            pruned.append(np.asarray(p))
            full.append(np.asarray(f))
    for p, f in queued:
        pruned.append(np.asarray(p))
        full.append(np.asarray(f))
    return np.concatenate(pruned, axis=1), np.concatenate(full, axis=1)


def scores(config: dict, seed: int, n_pages: int, chunk: int, codebook,
           queries, variant: str = "reference"):
    """`adc_rerank.scores`, its pages scored over every chip."""
    emb, q_mask = jnp.asarray(queries[0]), jnp.asarray(queries[1])
    precision = (jax.lax.Precision.HIGH if variant == "high"
                 else jax.lax.Precision.HIGHEST)
    table = jnp.einsum("qtd,kd->qtk", emb, jnp.asarray(codebook),
                       precision=precision)
    if variant == "bf16":
        table = jax.lax.reduce_precision(table, exponent_bits=8,
                                         mantissa_bits=7)
    bits = max(1, (codebook.shape[0] - 1).bit_length())
    pruned, full = corpus_scores(config, seed, n_pages, chunk, codebook,
                                 table, q_mask, bits)
    hpc = config["hpc"]
    n_cand = config["top_k"] if not hpc["rerank"] else max(
        config["top_k"], hpc["rerank"])
    return {"candidates": pruned, "final": full, "n_cand": n_cand}
