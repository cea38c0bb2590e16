"""Streaming ADC scan engine: blocked score + top-k fusion.

Every backend's scoring hot path runs through this module. The naive jnp
forms in core/late_interaction.py materialise a (B, Mq, N, Md) similarity
tensor (the `table[:, :, codes]` gather) — ~131 GB at B=8, Mq=32, Md=128,
N=1M — which caps the corpus at whatever fits in device memory *per query
batch*. This engine instead sweeps the corpus in fixed-size doc blocks
under one `lax.scan`:

  * each block is scored by an `impl` dispatcher — the Pallas
    `quantized_maxsim_pallas` kernel on TPU (`auto`), the blocked jnp
    gather elsewhere (`jnp`), or the kernel's interpreter (`interpret`,
    tests only);
  * top-k is folded into the sweep: a running (B, k) merge buffer is
    top-k'd against each block's (B, block) scores, so neither the
    (B, Mq, N, Md) similarity intermediate NOR the (B, N) score matrix
    ever exists. Peak scan memory is O(B * Mq * block_docs * Md); corpus
    capacity is bounded by the codes alone, O(N * Md) bytes.

Numerical contract: per-document scores are bit-identical to the
unblocked oracles (blocking the doc axis does not touch any per-doc
reduction), and the merge preserves `lax.top_k`'s lowest-index
tie-breaking — blocks are visited in doc order and the carried buffer
sits before the new block in each merge, so equal scores resolve to the
lowest doc index exactly as one global top_k would. The two layouts:

  * shared corpus  — codes (N, Md), every query scores every doc
    (flat / float_flat / hamming);
  * per-query candidates — codes (B, P, Md), each query scores its own
    pool (ivf probed buckets, hnsw beam survivors, facade rerank).

Sentinel contract (IndexBackend.search): result rows beyond the valid
pool carry doc_id -1; their score is the merge buffer's init value
(-inf for float scores), strictly below any real document's score — so a
degenerate all-patches-masked document (score ~ Mq * NEG_INF, finite)
still outranks the sentinel and is returned when k allows, matching the
unblocked oracle. Documents with `valid=False` (empty bucket slots,
unreachable beam rows) score exactly NEG_INF with id -1, the v0
convention. See docs/design.md §2.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import late_interaction as li
from repro.kernels import hamming as hamming_k
from repro.kernels import maxsim as maxsim_k
from repro.kernels import quantized_maxsim as qmaxsim_k
from repro.kernels import vmem

Array = jax.Array
NEG_INF = li.NEG_INF


@dataclasses.dataclass(frozen=True)
class ScanConfig:
    """Static knobs of the streaming scan (hashable — jit-static).

    block_docs: documents scored per sweep step. Peak scan memory is
        O(B * Mq * block_docs * Md) — the default keeps an 8x32-query
        batch over Md=128 patches around 128 MB of block similarities.
    impl: "auto" (Pallas kernel on TPU, blocked jnp elsewhere),
        "pallas", "jnp", or "interpret" (Pallas interpreter, tests).
    """

    block_docs: int = 256
    impl: str = "auto"


DEFAULT = ScanConfig()


def resolve_impl(impl: str) -> str:
    """Resolve the dispatcher key to a concrete block scorer.

    The single auto->pallas-on-TPU policy for the repo: kernels/ops.py
    delegates here too. "ref" (ops.py's name for the compiled-XLA
    oracle) is accepted as an alias of "jnp".
    """
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if impl == "ref":
        return "jnp"
    if impl not in ("pallas", "jnp", "interpret"):
        raise ValueError(
            f"unknown scan impl {impl!r}; expected auto|pallas|jnp|"
            "interpret (or ref, an alias of jnp)")
    return impl


def score_sentinel(dtype) -> Array:
    """Merge-buffer init value: below every representable real score."""
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.array(jnp.iinfo(dtype).min, dtype)
    return jnp.array(-jnp.inf, dtype)


def _kernel_tile(t: int, default: int, fits=None, *, lane: bool = False
                 ) -> int:
    """Inner Pallas doc tile for a t-doc block: the largest divisor of t,
    at most ``default``, that the TPU compiler accepts and that fits.

    ``lane=True`` marks kernels whose doc tile is the lane dim of their
    blocks (the ADC and hamming kernels score codes transposed), so a
    tile must be a multiple of 128 or t itself; t itself is the answer
    whenever t <= default. ``fits(tile) -> bool`` is the kernel's VMEM
    predicate (its ``*_vmem_bytes`` footprint vs
    ``kernels.vmem.VMEM_BUDGET_BYTES``): the largest legal tile that
    fits wins; if none fits, the smallest legal one is returned and the
    kernel's own ``ValueError`` surfaces the computed footprint.
    """
    legal = [d for d in range(min(t, default), 0, -1)
             if t % d == 0 and (not lane or d == t or d % vmem.LANES == 0)]
    if not legal:                    # t > default, no 128-multiple divides
        legal = [t]
    for tile in legal:
        if fits is None or fits(tile):
            return tile
    return legal[-1]


# ---------------------------------------------------------------------------
# The streaming sweep
# ---------------------------------------------------------------------------

def _streaming_topk(score_block, payload: tuple, doc_ids: Array,
                    valid: Array, *, b: int, n: int, k: int, block_docs: int,
                    per_query: bool, score_dtype,
                    carry: Optional[Tuple[Array, Array]] = None
                    ) -> Tuple[Array, Array]:
    """lax.scan over doc blocks with a running (B, k) top-k merge buffer.

    score_block(*payload_block) -> (B, T) scores for one block; payload
    leaves have the doc axis at dim 1 (per_query) or dim 0 (shared).

    `carry`, if given, seeds the merge buffer with a previous sweep's
    (scores (B, k), ids (B, k)) — the cross-segment continuation used by
    the segmented searches (core/index.py): sweeping segment s+1 with
    segment s's buffer as carry is bit-identical to one sweep over the
    concatenated corpus, because the carried buffer sits first in every
    merge (ties resolve to the earlier segment, i.e. the lower global
    position, exactly as one global lax.top_k would).
    """
    sent = score_sentinel(score_dtype)
    if carry is not None:
        init = (carry[0].astype(score_dtype), carry[1].astype(jnp.int32))
    else:
        init = (jnp.full((b, k), sent, score_dtype),
                jnp.full((b, k), -1, jnp.int32))
    if n == 0:
        return init
    block = max(1, min(block_docs, n))
    axis = 1 if per_query else 0
    doc_ids = doc_ids.astype(jnp.int32)
    invalid_score = jnp.array(NEG_INF, score_dtype) if \
        jnp.issubdtype(jnp.dtype(score_dtype), jnp.floating) else sent

    def merge(carry, start, t):
        """Score docs [start, start+t) and fold into the (B, k) buffer.

        Device stages, as `jax.named_scope`s: `scan.kernel` scores the
        block, `scan.merge` folds the scores into the buffer. (The code
        kernels' cast and transpose of the block nest inside
        `scan.kernel` as their own `kernel.layout`.)"""
        top_s, top_i = carry
        blk = tuple(jax.lax.dynamic_slice_in_dim(a, start, t, axis)
                    for a in payload)
        ids = jax.lax.dynamic_slice_in_dim(doc_ids, start, t,
                                           doc_ids.ndim - 1)
        v = jax.lax.dynamic_slice_in_dim(valid, start, t, valid.ndim - 1)
        with jax.named_scope("scan.kernel"):
            s = score_block(*blk)                             # (B, T)
        with jax.named_scope("scan.merge"):
            if v.ndim == 1:
                v = jnp.broadcast_to(v[None], s.shape)
            if ids.ndim == 1:
                ids = jnp.broadcast_to(ids[None], s.shape)
            # Caller-invalid slots (empty buckets, unreachable beam rows)
            # score exactly NEG_INF — the v0 convention. (Unfilled buffer
            # rows keep the init sentinel, strictly below every real doc.)
            s = jnp.where(v, s, invalid_score)
            ids = jnp.where(v, ids, -1)
            # Carried buffer first: equal scores resolve to the earlier
            # (lower-id) document, matching one global lax.top_k.
            cat_s = jnp.concatenate([top_s, s], axis=1)
            cat_i = jnp.concatenate([top_i, ids], axis=1)
            new_s, sel = jax.lax.top_k(cat_s, k)
            return new_s, jnp.take_along_axis(cat_i, sel, axis=1)

    # Full blocks sweep under lax.scan; a ragged N % block tail is scored
    # once at its natural (static) size — no padded corpus copy, no
    # in-range masking.
    n_full, tail = divmod(n, block)
    carry = init
    if n_full:
        carry, _ = jax.lax.scan(
            lambda c, j: (merge(c, j * block, block), None),
            carry, jnp.arange(n_full))
    if tail:
        carry = merge(carry, n_full * block, tail)
    return carry


def _prep(n: int, doc_ids: Optional[Array], valid: Optional[Array],
          per_query: bool, b: int) -> Tuple[Array, Array]:
    if doc_ids is None:
        doc_ids = jnp.arange(n, dtype=jnp.int32)
    if valid is None:
        valid = jnp.ones((b, n) if per_query and doc_ids.ndim == 2
                         else (n,), bool)
    return doc_ids, valid


# ---------------------------------------------------------------------------
# ADC (quantized) scan — the paper's hot path
# ---------------------------------------------------------------------------

def _adc_reduce(sim, d_mask_btm, q_mask):
    """Shared ADC tail: masked per-patch max, query-weighted sum.

    sim (B, Mq, T, Md) gathered table values; d_mask_btm broadcastable
    to (B, T, 1, Md) — li.quantized_maxsim minus the table build/gather.
    """
    sim = jnp.moveaxis(sim, 2, 1)                         # (B, T, Mq, Md)
    sim = jnp.where(d_mask_btm, sim, NEG_INF)
    per_q = jnp.max(sim, axis=-1)
    per_q = per_q * q_mask[:, None, :].astype(per_q.dtype)
    return jnp.sum(per_q, axis=-1)


def quantized_maxsim_topk(q: Array, q_mask: Array, codes: Array,
                          d_mask: Array, codebook: Array, *, k: int,
                          doc_ids: Optional[Array] = None,
                          valid: Optional[Array] = None,
                          scan: Optional[ScanConfig] = None,
                          carry: Optional[Tuple[Array, Array]] = None
                          ) -> Tuple[Array, Array]:
    """Streaming fused ADC MaxSim top-k.

    q (B, Mq, D), q_mask (B, Mq) bool, codebook (K, D);
    codes/d_mask (N, Md) shared or (B, P, Md) per-query candidates.
    Optional doc_ids ((N,) or (B, P)) map scan positions to global ids;
    optional valid ((N,) or (B, P)) marks real pool slots; optional
    carry seeds the merge buffer with a previous sweep's (B, k) result
    (the cross-segment continuation — see _streaming_topk).
    -> (scores (B, k) f32, doc_ids (B, k) i32) per IndexBackend.search.
    """
    scan = scan if scan is not None else DEFAULT
    mode = resolve_impl(scan.impl)
    per_query = codes.ndim == 3
    b = q.shape[0]
    n = codes.shape[1] if per_query else codes.shape[0]
    with jax.named_scope("search.table"):
        table = li.adc_table(q, codebook)                 # (B, Mq, K)
    doc_ids, valid = _prep(n, doc_ids, valid, per_query, b)

    if mode == "jnp":
        if per_query:
            def score_block(c, m):
                sim = jax.vmap(lambda tab, cc: tab[:, cc])(
                    table, c.astype(jnp.int32))           # (B, Mq, T, Md)
                return _adc_reduce(sim, m[:, :, None, :], q_mask)
        else:
            def score_block(c, m):
                sim = table[:, :, c.astype(jnp.int32)]    # (B, Mq, T, Md)
                return _adc_reduce(sim, m[None, :, None, :], q_mask)
    else:
        interpret = mode == "interpret"
        qm_f = q_mask.astype(jnp.float32)
        mq_n, k_n = table.shape[1], table.shape[2]
        md_n = codes.shape[-1]

        def qfits(tile):
            # the tile is sized for one query a step; the kernel then
            # scores as many queries a step as fit (query_group)
            return vmem.fits(qmaxsim_k.qmaxsim_vmem_bytes(
                tile, mq_n, k_n, md_n, g=1))

        if per_query:
            def score_block(c, m):
                def one(tab, qm1, cc, mm):
                    tile = _kernel_tile(cc.shape[0], 256, qfits, lane=True)
                    return qmaxsim_k.quantized_maxsim_pallas(
                        tab[None], qm1[None], cc.astype(jnp.int32),
                        mm.astype(jnp.float32), block_docs=tile,
                        interpret=interpret,
                        name="quantized_maxsim_pallas_pool")[0]
                return jax.vmap(one)(table, qm_f, c, m)
        else:
            def score_block(c, m):
                tile = _kernel_tile(c.shape[0], 256, qfits, lane=True)
                return qmaxsim_k.quantized_maxsim_pallas(
                    table, qm_f, c.astype(jnp.int32), m.astype(jnp.float32),
                    block_docs=tile, interpret=interpret,
                    name="quantized_maxsim_pallas_scan")

    return _streaming_topk(score_block, (codes, d_mask), doc_ids, valid,
                           b=b, n=n, k=k, block_docs=scan.block_docs,
                           per_query=per_query, score_dtype=jnp.float32,
                           carry=carry)


# ---------------------------------------------------------------------------
# Float scan (uncompressed baseline)
# ---------------------------------------------------------------------------

def maxsim_topk(q: Array, q_mask: Array, docs: Array, d_mask: Array, *,
                k: int, doc_ids: Optional[Array] = None,
                valid: Optional[Array] = None,
                scan: Optional[ScanConfig] = None,
                carry: Optional[Tuple[Array, Array]] = None
                ) -> Tuple[Array, Array]:
    """Streaming float MaxSim top-k.

    docs/d_mask are either a shared (N, Md, D) corpus or (B, P, Md, D)
    per-query candidate pools (the cascade's float rerank stage) — same
    two layouts as `quantized_maxsim_topk`.
    """
    scan = scan if scan is not None else DEFAULT
    mode = resolve_impl(scan.impl)
    per_query = docs.ndim == 4
    b = q.shape[0]
    n = docs.shape[1] if per_query else docs.shape[0]
    doc_ids, valid = _prep(n, doc_ids, valid, per_query, b)

    if mode == "jnp":
        if per_query:
            def score_block(d, m):
                return jax.vmap(
                    lambda q1, qm1, d1, m1: li.maxsim(q1[None], qm1[None],
                                                      d1, m1)[0]
                )(q, q_mask, d, m)
        else:
            def score_block(d, m):
                return li.maxsim(q, q_mask, d, m)
    else:
        interpret = mode == "interpret"
        qm_f = q_mask.astype(jnp.float32)

        mq_n, md_n, d_n = q.shape[1], docs.shape[-2], docs.shape[-1]

        def mfits(tile):
            return vmem.fits(maxsim_k.maxsim_vmem_bytes(
                tile, mq_n, md_n, d_n))

        if per_query:
            def score_block(d, m):
                def one(q1, qm1, d1, m1):
                    tile = _kernel_tile(d1.shape[0], 16, mfits)
                    return maxsim_k.maxsim_pallas(
                        q1[None], qm1[None], d1, m1.astype(jnp.float32),
                        block_docs=tile, interpret=interpret)[0]
                return jax.vmap(one)(q, qm_f, d, m)
        else:
            def score_block(d, m):
                tile = _kernel_tile(d.shape[0], 16, mfits)
                return maxsim_k.maxsim_pallas(q, qm_f, d,
                                              m.astype(jnp.float32),
                                              block_docs=tile,
                                              interpret=interpret)

    return _streaming_topk(score_block, (docs, d_mask), doc_ids, valid,
                           b=b, n=n, k=k, block_docs=scan.block_docs,
                           per_query=per_query, score_dtype=jnp.float32,
                           carry=carry)


# ---------------------------------------------------------------------------
# Hamming (binary) scan
# ---------------------------------------------------------------------------

def hamming_maxsim_topk(q_codes: Array, q_mask: Array, d_codes: Array,
                        d_mask: Array, *, bits: int, k: int,
                        doc_ids: Optional[Array] = None,
                        valid: Optional[Array] = None,
                        scan: Optional[ScanConfig] = None,
                        carry: Optional[Tuple[Array, Array]] = None
                        ) -> Tuple[Array, Array]:
    """Streaming binary MaxSim top-k.

    d_codes/d_mask are either a shared (N, Md) code corpus or (B, P, Md)
    per-query candidate pools — the same two layouts as
    `quantized_maxsim_topk`. Scores are int32 on every impl (v0's
    li.binary_maxsim dtype; the sentinel is the int32 minimum). The
    Pallas kernel accumulates in f32 (its documented contract); its
    block scores are clamped to the int32 range and cast — real scores
    (|s| <= bits * Mq) are exact, only the degenerate
    all-patches-masked sums (~ -Mq * 2^20) can lose ULPs.
    """
    scan = scan if scan is not None else DEFAULT
    mode = resolve_impl(scan.impl)
    per_query = d_codes.ndim == 3
    b = q_codes.shape[0]
    n = d_codes.shape[1] if per_query else d_codes.shape[0]
    doc_ids, valid = _prep(n, doc_ids, valid, per_query, b)
    ii = jnp.iinfo(jnp.int32)

    if mode == "jnp":
        if per_query:
            def score_block(d, m):
                return jax.vmap(
                    lambda q1, qm1, d1, m1: li.binary_maxsim(
                        q1[None], qm1[None], d1, m1, bits)[0]
                )(q_codes, q_mask, d, m)
        else:
            def score_block(d, m):
                return li.binary_maxsim(q_codes, q_mask, d, m, bits)
    else:
        interpret = mode == "interpret"
        qm_f = q_mask.astype(jnp.float32)

        mq_n, md_n = q_codes.shape[1], d_codes.shape[-1]

        def hfits(tile):
            return vmem.fits(hamming_k.hamming_vmem_bytes(
                tile, mq_n, md_n))

        if per_query:
            def score_block(d, m):
                def one(q1, qm1, d1, m1):
                    tile = _kernel_tile(d1.shape[0], 256, hfits, lane=True)
                    return hamming_k.hamming_maxsim_pallas(
                        q1[None], qm1[None], d1.astype(jnp.int32),
                        m1.astype(jnp.float32), bits=bits,
                        block_docs=tile, interpret=interpret,
                        name="hamming_maxsim_pallas_pool")[0]
                out = jax.vmap(one)(q_codes, qm_f, d, m)
                return jnp.maximum(out, float(ii.min)).astype(jnp.int32)
        else:
            def score_block(d, m):
                tile = _kernel_tile(d.shape[0], 256, hfits, lane=True)
                out = hamming_k.hamming_maxsim_pallas(
                    q_codes, qm_f, d.astype(jnp.int32), m.astype(jnp.float32),
                    bits=bits, block_docs=tile, interpret=interpret,
                    name="hamming_maxsim_pallas_scan")
                # only the lower bound can be exceeded (NEG_INF-masked
                # sums); -2^31 is f32-exact, real scores are far below 2^31
                return jnp.maximum(out, float(ii.min)).astype(jnp.int32)

    return _streaming_topk(score_block, (d_codes, d_mask), doc_ids, valid,
                           b=b, n=n, k=k, block_docs=scan.block_docs,
                           per_query=per_query, score_dtype=jnp.int32,
                           carry=carry)
