"""Plain reference of the flat ADC search with rerank (`colpali-hpc`).

The query-centroid table T = Q C^T in float32 at full precision; the
first stage ranks pages by MaxSim over their pruned patches' codes and
keeps max(top_k, rerank) candidates; the rerank orders those by MaxSim
over all their patches' codes. The control is the same computation with
the table at `Precision.HIGH` (three bfloat16 passes where the
configuration states float32 at HIGHEST); `bf16`, the table rounded to
bfloat16 (one pass), is read beside it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip import refcore

# set from the readings in PERF.md ("How correct is decided")
LIMITS = {"score_gap": 2e-6, "codebook_excess": 0.25}
VARIANTS = ("reference", "high", "bf16")


def scores(config: dict, seed: int, n_pages: int, chunk: int, codebook,
           queries, variant: str = "reference"):
    """{"candidates": (Q, N) first-stage scores, "final": (Q, N) rerank
    scores, "n_cand": candidates kept} for the query embeddings
    `queries` (emb (Q, Mq, D), mask (Q, Mq))."""
    emb, q_mask = jnp.asarray(queries[0]), jnp.asarray(queries[1])
    precision = (jax.lax.Precision.HIGH if variant == "high"
                 else jax.lax.Precision.HIGHEST)
    table = jnp.einsum("qtd,kd->qtk", emb, jnp.asarray(codebook),
                       precision=precision)
    if variant == "bf16":
        table = jax.lax.reduce_precision(table, exponent_bits=8,
                                         mantissa_bits=7)
    bits = max(1, (codebook.shape[0] - 1).bit_length())
    pruned, full = refcore.corpus_scores(config, seed, n_pages, chunk,
                                         codebook, table, q_mask, bits)
    hpc = config["hpc"]
    n_cand = config["top_k"] if not hpc["rerank"] else max(
        config["top_k"], hpc["rerank"])
    return {"candidates": pruned, "final": full, "n_cand": n_cand}
