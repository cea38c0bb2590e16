"""Pallas TPU kernel: fused decode-and-score ADC MaxSim — the paper's hot
path, TPU-adapted (docs/design.md §2).

A float corpus scan reads 4*D = 512 B/patch from HBM; this kernel reads the
1-byte code instead and resolves it against the query-centroid table
T = Q @ C^T (built once per query batch, (Mq, K) f32 <= 64 KB) held in VMEM.
HBM traffic drops ~32x at unchanged MaxSim semantics — converting the
paper's storage win into the bandwidth win that a memory-bound scan needs.

The in-kernel "gather" is realised as a one-hot matmul on the MXU instead
of a serialised VPU gather — the standard TPU idiom for small-table
lookups. Codes and mask are scored transposed, (Md, N), so one patch
position of a `block_docs` doc tile is one lane-dense row:

    onehot_j = (iota_K[:, None] == codes[j, :])      # (K, block_docs)
    sim_j    = T @ onehot_j                          # (Mq, block_docs)

and the kernel folds `sim_j` into a running masked max over j < Md; the
query-masked sum over Mq then leaves a (1, block_docs) score row. Every
block's last two dims are (8, 128)-aligned or whole — what the TPU
compiler accepts — and the per-step VMEM is O(K * block_docs), so wide
geometries (Md=615, K=512) fit without shrinking the tile.

Precision: the one-hot is exact in bf16, and the f32 table is split into
three bf16 terms (hi + mid + lo == T exactly), so three bf16 MXU passes
with f32 accumulation return the f32 table entries bit for bit; a
default-precision f32 matmul would round T to bf16.

Grid: (N // block_docs, B), queries innermost: each doc tile is fetched
from HBM once per batch, and the (3*Mq, K) table block is what changes
between steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import vmem

NEG_INF = -1e30


def split_bf16(x):
    """(hi, mid, lo) bf16 with hi + mid + lo == x (f32) exactly.

    The rounding is spelled `reduce_precision`, not an f32 -> bf16 -> f32
    round trip: XLA on TPU drops such a round trip as excess precision,
    which would leave mid = lo = 0 and the table rounded to bf16.
    """
    def to_bf16_grid(v):
        return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)

    hi = to_bf16_grid(x)
    mid = to_bf16_grid(x - hi)
    lo = (x - hi) - mid
    return (hi.astype(jnp.bfloat16), mid.astype(jnp.bfloat16),
            lo.astype(jnp.bfloat16))


def qmaxsim_vmem_bytes(block_docs: int, mq: int, k: int, md: int) -> int:
    """Per-grid-step VMEM footprint of ``_qmaxsim_kernel`` in bytes.

    Double-buffered blocks (split table, q_mask column, transposed
    codes and mask, score row) plus the per-patch temporaries: the
    (K, block_docs) iota, compare and bf16 one-hot, the (3*Mq,
    block_docs) matmul result, and the similarity/running-max rows.
    """
    tb, mq = vmem.tile_bytes, vmem.pad_rows(mq)
    blocks = (tb((3 * mq, k), 2) + tb((mq, 1), 4)
              + 2 * tb((md, block_docs), 4) + tb((1, block_docs), 4))
    onehot = 2 * tb((k, block_docs), 4) + tb((k, block_docs), 2)
    sims = tb((3 * mq, block_docs), 4) + 3 * tb((mq, block_docs), 4)
    return vmem.DOUBLE_BUFFER * blocks + onehot + sims


def _qmaxsim_kernel(tab_ref, qm_ref, codes_ref, dm_ref, out_ref):
    # tab_ref:   (1, 3*Mq, K) bf16 — the table's hi/mid/lo split
    # qm_ref:    (1, Mq, 1) f32 query-token mask, a column
    # codes_ref: (Md, T) int32, docs on lanes
    # dm_ref:    (Md, T) f32 patch mask, same layout
    # out_ref:   (1, 1, T) f32
    tab = tab_ref[0]
    mq, k = tab.shape[0] // 3, tab.shape[1]
    md, t = codes_ref.shape
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (k, t), 0)

    def patch(j, acc):
        row = codes_ref[pl.ds(j, 1), :]                   # (1, T)
        onehot = (iota_k == row).astype(jnp.bfloat16)     # (K, T)
        s3 = jnp.dot(tab, onehot, preferred_element_type=jnp.float32)
        sim = (s3[:mq] + s3[mq:2 * mq]) + s3[2 * mq:]     # (Mq, T) == T[:, c]
        sim = jnp.where(dm_ref[pl.ds(j, 1), :] > 0, sim, NEG_INF)
        return jnp.maximum(acc, sim)

    per_q = jax.lax.fori_loop(0, md, patch,
                              jnp.full((mq, t), NEG_INF, jnp.float32))
    out_ref[0] = jnp.sum(per_q * qm_ref[0], axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_docs", "interpret"))
def quantized_maxsim_pallas(table, q_mask, codes, d_mask, *,
                            block_docs: int = 256, interpret: bool = False):
    """table (B, Mq, K) f32, q_mask (B, Mq) f32, codes (N, Md) int,
    d_mask (N, Md) f32 -> scores (B, N) f32.  N % block_docs == 0; on
    the chip block_docs is a multiple of 128 or N."""
    b, mq, k = table.shape
    n, md = codes.shape
    name = "quantized_maxsim_pallas"
    vmem.check_divisible(n, block_docs, kernel=name)
    if not interpret:
        vmem.check_lane_tile(n, block_docs, kernel=name)
    vmem.check_vmem(
        qmaxsim_vmem_bytes(block_docs, mq, k, md), kernel=name,
        detail=f"block_docs={block_docs}, Mq={mq}, K={k}, Md={md}; the "
               f"one-hot tile is ({k}, {block_docs})")
    mq_p = vmem.pad_rows(mq)
    rows = ((0, 0), (0, mq_p - mq))
    tab3 = jnp.concatenate(
        split_bf16(jnp.pad(table.astype(jnp.float32), rows + ((0, 0),))),
        axis=1)                                           # (B, 3*Mq_p, K)
    qm = jnp.pad(q_mask.astype(jnp.float32), rows)[:, :, None]
    out = pl.pallas_call(
        _qmaxsim_kernel,
        grid=(n // block_docs, b),
        in_specs=[
            pl.BlockSpec((1, 3 * mq_p, k), lambda j, i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, mq_p, 1), lambda j, i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((md, block_docs), lambda j, i: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((md, block_docs), lambda j, i: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, block_docs), lambda j, i: (i, 0, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, 1, n), jnp.float32),
        interpret=interpret,
    )(tab3, qm, codes.astype(jnp.int32).T, d_mask.astype(jnp.float32).T)
    return out[:, 0, :]
