"""Late-interaction (MaxSim) scoring — float, quantized (ADC), and binary.

score(q, d) = sum_i  max_j  <q_i, d_j>        (ColBERT / ColPali)

Variants implemented here are the canonical jnp forms; the tiled Pallas
kernels in kernels/{maxsim,quantized_maxsim,hamming}.py are drop-in
replacements for the inner scan and are validated against these.

Quantized scoring uses the ADC (asymmetric distance computation) trick:
queries stay float, documents are 1-byte codes. We precompute the
query-token x centroid table  T = Q @ C^T  (Mq x K dots, once per query),
after which scoring a document patch is a pure table gather — zero matmul
FLOPs per document. This is the TPU-native realisation of the paper's
"decode each code back to its centroid then search" (§III-E1): instead of
materialising a decoded float corpus in HBM (undoing the 32x storage win),
the decode is folded into a VMEM table lookup. See docs/design.md §2.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.core import binary as binary_mod

Array = jax.Array
NEG_INF = -1e30


def _masked_max(sim: Array, d_mask: Array) -> Array:
    """Max over the last (doc-patch) axis, ignoring invalid patches.

    sim: (..., Mq, Md), d_mask broadcastable (..., 1, Md) -> (..., Mq).
    """
    sim = jnp.where(d_mask, sim, NEG_INF)
    return jnp.max(sim, axis=-1)


def maxsim(q: Array, q_mask: Array, d: Array, d_mask: Array) -> Array:
    """Float late interaction.

    Args:
      q:      (B, Mq, D) query patch embeddings.
      q_mask: (B, Mq) bool.
      d:      (N, Md, D) document patch embeddings.
      d_mask: (N, Md) bool.
    Returns:
      scores (B, N) float32.
    """
    sim = jnp.einsum("bqd,nkd->bnqk", q, d,
                     preferred_element_type=jnp.float32)
    per_q = _masked_max(sim, d_mask[None, :, None, :])        # (B, N, Mq)
    per_q = per_q * q_mask[:, None, :].astype(per_q.dtype)
    return jnp.sum(per_q, axis=-1)


def adc_table(q: Array, codebook: Array) -> Array:
    """Query-token x centroid similarity table T (B, Mq, K).

    Full f32 precision: at the default precision the TPU rounds both
    operands to bf16 (~1% error in T on the chip), and every ADC score
    inherits T's error. The table is B*Mq*K*D MACs per batch — no cost.
    """
    return jnp.einsum("bqd,kd->bqk", q, codebook,
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def quantized_maxsim(q: Array, q_mask: Array, d_codes: Array, d_mask: Array,
                     codebook: Array) -> Array:
    """ADC late interaction over a quantized corpus.

    Args:
      q:        (B, Mq, D) float queries.
      d_codes:  (N, Md) uint8/uint16 centroid indices.
      codebook: (K, D).
    Returns:
      scores (B, N) float32 — identical (up to fp assoc.) to
      maxsim(q, decode(d_codes)).
    """
    table = adc_table(q, codebook)                            # (B, Mq, K)
    codes = d_codes.astype(jnp.int32)                         # (N, Md)
    # Gather: sim[b, n, i, j] = table[b, i, codes[n, j]]
    sim = table[:, :, codes]                                  # (B, Mq, N, Md)
    sim = jnp.moveaxis(sim, 2, 1)                             # (B, N, Mq, Md)
    per_q = _masked_max(sim, d_mask[None, :, None, :])
    per_q = per_q * q_mask[:, None, :].astype(per_q.dtype)
    return jnp.sum(per_q, axis=-1)


def quantized_maxsim_decode(q: Array, q_mask: Array, d_codes: Array,
                            d_mask: Array, codebook: Array) -> Array:
    """Decode-then-score variant (the paper's literal §III-E1 path).

    Equivalent to quantized_maxsim; kept as an equivalence oracle and for
    measuring the HBM-traffic delta in benchmarks/roofline.py.
    """
    d = jnp.take(codebook, d_codes.astype(jnp.int32), axis=0)
    return maxsim(q, q_mask, d, d_mask)


def binary_maxsim(q_codes: Array, q_mask: Array, d_codes: Array,
                  d_mask: Array, bits: int) -> Array:
    """Hamming-similarity late interaction (binary mode, §III-D).

    sim(i, j) = bits - hamming(q_i, d_j); scores are int32 sums.
    """
    sim = binary_mod.hamming_sim_matrix(
        q_codes[:, None, :], d_codes[None, :, :], bits)       # (B, N, Mq, Md)
    sim = jnp.where(d_mask[None, :, None, :], sim, jnp.int32(-(2 ** 20)))
    per_q = jnp.max(sim, axis=-1)                             # (B, N, Mq)
    per_q = per_q * q_mask[:, None, :].astype(per_q.dtype)
    return jnp.sum(per_q, axis=-1).astype(jnp.int32)


def single_vector_score(q: Array, q_mask: Array, d: Array, d_mask: Array) -> Array:
    """DistilCol-style single-vector baseline: mean-pool both sides, dot.

    (B, Mq, D) x (N, Md, D) -> (B, N). Used as the paper's DistilCol stand-in.
    """
    qm = q_mask[..., None].astype(q.dtype)
    dm = d_mask[..., None].astype(d.dtype)
    q_pool = jnp.sum(q * qm, axis=1) / jnp.maximum(jnp.sum(qm, axis=1), 1.0)
    d_pool = jnp.sum(d * dm, axis=1) / jnp.maximum(jnp.sum(dm, axis=1), 1.0)
    q_pool = q_pool / jnp.maximum(jnp.linalg.norm(q_pool, axis=-1, keepdims=True), 1e-9)
    d_pool = d_pool / jnp.maximum(jnp.linalg.norm(d_pool, axis=-1, keepdims=True), 1e-9)
    return q_pool @ d_pool.T


def late_interaction_flops(mq: int, md: int, d: int, n_docs: int) -> int:
    """FLOPs of one query's float late interaction over n_docs documents."""
    return 2 * mq * md * d * n_docs


def adc_flops(mq: int, md: int, d: int, k: int, n_docs: int) -> int:
    """FLOPs of ADC scoring: one table build + per-doc gathers (0 matmul)."""
    return 2 * mq * k * d  # table; gather/max/sum are O(mq*md*n_docs) adds
