"""Pallas TPU kernel: tiled float MaxSim late-interaction scan.

score(b, n) = sum_i q_mask[b,i] * max_j (d_mask[n,j] ? <q[b,i], d[n,j]> : -inf)

Tiling (docs/design.md §7): the query block for batch row b — (Mq, D) —
is resident in VMEM; documents stream through in tiles of `block_docs`
docs, (block_docs, Md, D). Per doc the kernel runs one (Mq, D) x (D, Md)
MXU matmul at full f32 precision, masks and max-reduces over Md, and
drops the query-masked sum into lane t of a (1, block_docs) score row.
At Md=616, D=128 a doc is 315 KB of f32, so the tile stays small (16
docs ≈ 10 MiB double-buffered); the score row is therefore written as a
whole (1, block_docs) block of a (N // block_docs, B, 1, block_docs)
output, which the TPU compiler accepts for any tile, and transposed back
to (B, N) outside.

Grid: (N // block_docs, B), queries innermost, so each doc tile is read
from HBM once per batch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import vmem

NEG_INF = -1e30


def maxsim_vmem_bytes(block_docs: int, mq: int, md: int, d: int) -> int:
    """Per-grid-step VMEM footprint of ``_maxsim_kernel`` in bytes:
    double-buffered blocks + the per-doc (Mq, Md) similarity
    temporaries (raw + masked) and the score row."""
    tb = vmem.tile_bytes
    blocks = (tb((mq, d), 4) + tb((mq, 1), 4) + tb((block_docs, md, d), 4)
              + tb((block_docs, 1, md), 4) + tb((1, block_docs), 4))
    temps = 2 * tb((mq, md), 4) + 2 * tb((1, block_docs), 4)
    return vmem.DOUBLE_BUFFER * blocks + temps


def _maxsim_kernel(q_ref, qm_ref, d_ref, dm_ref, out_ref):
    # q_ref:  (1, Mq, D)        VMEM
    # qm_ref: (1, Mq, 1)        query-token mask, a column
    # d_ref:  (T, Md, D)
    # dm_ref: (T, 1, Md)        patch mask, one row per doc
    # out_ref: (1, 1, 1, T)
    q = q_ref[0].astype(jnp.float32)                      # (Mq, D)
    qm = qm_ref[0]                                        # (Mq, 1)
    t = d_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, t), 1)

    def doc(i, row):
        sim = jax.lax.dot_general(q, d_ref[i].astype(jnp.float32),
                                  (((1,), (1,)), ((), ())),
                                  precision=jax.lax.Precision.HIGHEST,
                                  preferred_element_type=jnp.float32)
        sim = jnp.where(dm_ref[i] > 0, sim, NEG_INF)      # (Mq, Md)
        per_q = jnp.max(sim, axis=1, keepdims=True)       # (Mq, 1)
        s = jnp.sum(per_q * qm, axis=0, keepdims=True)    # (1, 1)
        return jnp.where(lane == i, s, row)

    out_ref[0, 0] = jax.lax.fori_loop(0, t, doc,
                                      jnp.zeros((1, t), jnp.float32))


@functools.partial(jax.jit, static_argnames=("block_docs", "interpret"))
def maxsim_pallas(q, q_mask, docs, d_mask, *, block_docs: int = 16,
                  interpret: bool = False):
    """q (B, Mq, D) f32, q_mask (B, Mq) f32, docs (N, Md, D) f32,
    d_mask (N, Md) f32 -> scores (B, N) f32.  N % block_docs == 0."""
    b, mq, dd = q.shape
    n, md, _ = docs.shape
    vmem.check_divisible(n, block_docs, kernel="maxsim_pallas")
    vmem.check_vmem(
        maxsim_vmem_bytes(block_docs, mq, md, dd),
        kernel="maxsim_pallas",
        detail=f"block_docs={block_docs}, Mq={mq}, Md={md}, D={dd}; the "
               f"doc block is ({block_docs}, {md}, {dd}) f32")
    nb = n // block_docs
    out = pl.pallas_call(
        _maxsim_kernel,
        grid=(nb, b),
        in_specs=[
            pl.BlockSpec((1, mq, dd), lambda j, i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, mq, 1), lambda j, i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_docs, md, dd), lambda j, i: (j, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_docs, 1, md), lambda j, i: (j, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, block_docs),
                               lambda j, i: (j, i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((nb, b, 1, block_docs), jnp.float32),
        interpret=interpret,
    )(q.astype(jnp.float32), q_mask.astype(jnp.float32)[:, :, None],
      docs.astype(jnp.float32), d_mask.astype(jnp.float32)[:, None, :])
    return jnp.moveaxis(out[:, :, 0, :], 1, 0).reshape(b, n)
