"""Public jit'd wrappers for the Pallas kernels.

Responsibilities:
  * platform dispatch — real Pallas lowering on TPU, ``interpret=True``
    execution when requested (tests), pure-jnp oracle otherwise (CPU prod
    path: interpret mode is Python-slow, the oracle is compiled XLA);
  * padding — corpora are padded to the doc-block multiple with masked-out
    rows (scores for pad rows are dropped before returning);
  * dtype hygiene — bool masks -> f32 0/1, codes -> int32 lanes.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels import hamming as hamming_k
from repro.kernels import kmeans_assign as kmeans_k
from repro.kernels import maxsim as maxsim_k
from repro.kernels import quantized_maxsim as qmaxsim_k
from repro.kernels import ref

Array = jax.Array
Impl = Literal["auto", "pallas", "interpret", "ref"]


def _resolve(impl: Impl) -> str:
    """Platform dispatch, shared with the streaming scan engine.

    core/scan.py owns the single auto->pallas-on-TPU policy; this module
    spells the compiled-XLA oracle "ref" where the engine says "jnp"
    (the resolver accepts both). Lazy import: repro.core.scan imports
    kernel modules from this package, so binding it at module top would
    race package init.
    """
    from repro.core.scan import resolve_impl
    mode = resolve_impl(impl)
    return "ref" if mode == "jnp" else mode


def _pad_docs(arrs, n, block):
    """Pad dim 0 of each array to the next multiple of `block`."""
    n_pad = (-n) % block
    if n_pad == 0:
        return arrs, n
    out = []
    for a in arrs:
        pad_width = [(0, n_pad)] + [(0, 0)] * (a.ndim - 1)
        out.append(jnp.pad(a, pad_width))
    return out, n + n_pad


@functools.partial(jax.jit, static_argnames=("impl", "block_docs"))
def maxsim(q: Array, q_mask: Array, docs: Array, d_mask: Array, *,
           impl: Impl = "auto", block_docs: int = 16) -> Array:
    """Float MaxSim scores (B, N)."""
    mode = _resolve(impl)
    qm = q_mask.astype(jnp.float32)
    dm = d_mask.astype(jnp.float32)
    if mode == "ref":
        return ref.maxsim(q, qm, docs, dm)
    n = docs.shape[0]
    (docs_p, dm_p), n_p = _pad_docs((docs, dm), n, block_docs)
    out = maxsim_k.maxsim_pallas(q, qm, docs_p, dm_p,
                                 block_docs=block_docs,
                                 interpret=(mode == "interpret"))
    return out[:, :n]


@functools.partial(jax.jit, static_argnames=("impl", "block_docs"))
def quantized_maxsim(q: Array, q_mask: Array, codes: Array, d_mask: Array,
                     codebook: Array, *, impl: Impl = "auto",
                     block_docs: int = 256) -> Array:
    """Fused ADC MaxSim scores (B, N) over a quantized corpus."""
    mode = _resolve(impl)
    qm = q_mask.astype(jnp.float32)
    dm = d_mask.astype(jnp.float32)
    table = jnp.einsum("bqd,kd->bqk", q.astype(jnp.float32),
                       codebook.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    if mode == "ref":
        return ref.quantized_maxsim(table, qm, codes, dm)
    n = codes.shape[0]
    (codes_p, dm_p), n_p = _pad_docs((codes.astype(jnp.int32), dm), n,
                                     block_docs)
    out = qmaxsim_k.quantized_maxsim_pallas(
        table, qm, codes_p, dm_p, block_docs=block_docs,
        interpret=(mode == "interpret"))
    return out[:, :n]


@functools.partial(jax.jit, static_argnames=("bits", "impl", "block_docs"))
def hamming_maxsim(q_codes: Array, q_mask: Array, d_codes: Array,
                   d_mask: Array, *, bits: int, impl: Impl = "auto",
                   block_docs: int = 256) -> Array:
    """Binary-mode MaxSim scores (B, N)."""
    mode = _resolve(impl)
    qm = q_mask.astype(jnp.float32)
    dm = d_mask.astype(jnp.float32)
    if mode == "ref":
        return ref.hamming_maxsim(q_codes, qm, d_codes, dm, bits)
    n = d_codes.shape[0]
    (codes_p, dm_p), n_p = _pad_docs((d_codes.astype(jnp.int32), dm), n,
                                     block_docs)
    out = hamming_k.hamming_maxsim_pallas(
        q_codes, qm, codes_p, dm_p, bits=bits, block_docs=block_docs,
        interpret=(mode == "interpret"))
    return out[:, :n]


@functools.partial(jax.jit, static_argnames=("impl", "block_n"))
def kmeans_assign(x: Array, centroids: Array, *, impl: Impl = "auto",
                  block_n: int = 256) -> Array:
    """Nearest-centroid codes (N,) int32."""
    mode = _resolve(impl)
    if mode == "ref":
        return ref.kmeans_assign(x, centroids)
    n = x.shape[0]
    (x_p,), n_p = _pad_docs((x,), n, block_n)
    out = kmeans_k.kmeans_assign_pallas(
        x_p, centroids, block_n=block_n, interpret=(mode == "interpret"))
    return out[:n]
