"""Plain reference of the Hamming MaxSim scan (`colpali-hpc-binary`).

Each query token and each patch is coded by its nearest centroid, a
b-bit string (b = ceil(log2 K)); sim = b - popcount(q XOR d); a page's
score is the int32 sum over query tokens of its best sim among its
pruned patches' codes. No rerank. The control drops the top bit: b - 1
bits a code, at K=512 the codes stored a byte each, the step down from
uint16 storage.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip import refcore

# exact integer scores; the codebook's limit is set from the readings in
# PERF.md ("How correct is decided")
LIMITS = {"score_gap": 0.0, "codebook_excess": 0.25}
VARIANTS = ("reference", "bits-1")


def scores(config: dict, seed: int, n_pages: int, chunk: int, codebook,
           queries, variant: str = "reference"):
    """{"candidates": (Q, N), "final": (Q, N), "n_cand": top_k}: the
    same int32 scores in both (there is no rerank)."""
    k = codebook.shape[0]
    bits = max(1, (k - 1).bit_length())
    if variant == "bits-1":
        bits -= 1
    cb = jnp.asarray(codebook)
    emb, q_mask = jnp.asarray(queries[0]), jnp.asarray(queries[1])
    q_codes = refcore.nearest(emb.reshape(-1, emb.shape[-1]), cb).reshape(
        emb.shape[:2]) & ((1 << bits) - 1)
    codes = jnp.arange(k, dtype=jnp.int32) & ((1 << bits) - 1)
    table = bits - jax.lax.population_count(
        q_codes[:, :, None] ^ codes[None, None, :])          # (Q, Mq, K)
    pruned, _ = refcore.corpus_scores(config, seed, n_pages, chunk, cb,
                                      table.astype(jnp.int32), q_mask, bits)
    if config["hpc"]["rerank"]:
        raise ValueError("the hamming reference has no rerank")
    return {"candidates": pruned, "final": pruned,
            "n_cand": config["top_k"]}
