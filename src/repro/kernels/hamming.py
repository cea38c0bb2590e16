"""Pallas TPU kernel: binary-mode Hamming MaxSim scan (paper §III-D).

sim(i, j) = bits - popcount(q_code_i XOR d_code_j), MaxSim-reduced exactly
like the float kernel. x86 POPCNT becomes `lax.population_count` on the VPU
(8x128 int32 lanes); there is no MXU work here — the scan is bandwidth-bound
on the 1-2 B/patch code stream, which is the point of the binary mode.

Codes arrive as int32 lanes (ops.py casts from the uint16 storage form; the
bit-packed on-disk layout is unpacked once at load, see core/binary.py).
Like the ADC kernel (kernels/quantized_maxsim.py) the doc codes are scored
transposed, (Md, N): per patch position the (Mq, 1) query-code column is
XOR'd against one lane-dense (1, block_docs) code row, and a running
masked max over Md leaves a (1, block_docs) score row — every block is
(8, 128)-aligned or whole, as the TPU compiler requires.

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


def hamming_vmem_bytes(block_docs: int, mq: int, md: int) -> int:
    """Per-grid-step VMEM footprint of ``_hamming_kernel`` in bytes:
    double-buffered blocks + the per-patch (Mq, block_docs) xor/popcount/
    masked-sim temporaries and the running max. The SMEM bits scalar is
    excluded (not VMEM)."""
    tb, mq = vmem.tile_bytes, vmem.pad_rows(mq)
    blocks = (2 * tb((mq, 1), 4) + 2 * tb((md, block_docs), 4)
              + tb((1, block_docs), 4))
    temps = 5 * tb((mq, block_docs), 4)
    return vmem.DOUBLE_BUFFER * blocks + temps


def _hamming_kernel(bits_ref, q_ref, qm_ref, d_ref, dm_ref, out_ref):
    # bits_ref: (1, 1) i32 in SMEM  — b = ceil(log2 K)
    # q_ref:  (1, Mq, 1) i32; qm_ref: (1, Mq, 1) f32 — columns
    # d_ref:  (Md, T) i32, docs on lanes; dm_ref: (Md, T) f32
    # out_ref: (1, 1, T) f32
    bits = bits_ref[0, 0]
    q = q_ref[0]                                          # (Mq, 1)
    md, t = d_ref.shape

    def patch(j, acc):
        x = jax.lax.population_count(
            jnp.bitwise_xor(q, d_ref[pl.ds(j, 1), :]))    # (Mq, T)
        sim = (bits - x).astype(jnp.float32)
        sim = jnp.where(dm_ref[pl.ds(j, 1), :] > 0, sim, NEG_INF)
        return jnp.maximum(acc, sim)

    per_q = jax.lax.fori_loop(
        0, md, patch, jnp.full((q.shape[0], t), NEG_INF, jnp.float32))
    out_ref[0] = jnp.sum(per_q * qm_ref[0], axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bits", "block_docs", "interpret"))
def hamming_maxsim_pallas(q_codes, q_mask, d_codes, d_mask, *, bits: int,
                          block_docs: int = 256, interpret: bool = False):
    """q_codes (B, Mq) int, d_codes (N, Md) int, masks f32 ->
    scores (B, N) f32.  N % block_docs == 0; on the chip block_docs is
    a multiple of 128 or N."""
    b, mq = q_codes.shape
    n, md = d_codes.shape
    name = "hamming_maxsim_pallas"
    vmem.check_divisible(n, block_docs, kernel=name)
    if not interpret:
        vmem.check_lane_tile(n, block_docs, kernel=name)
    vmem.check_vmem(
        hamming_vmem_bytes(block_docs, mq, md), kernel=name,
        detail=f"block_docs={block_docs}, Mq={mq}, Md={md}; the per-patch "
               f"xor/popcount temporaries are ({mq}, {block_docs}) i32")
    mask_b = (1 << bits) - 1
    rows = ((0, 0), (0, vmem.pad_rows(mq) - mq))
    qc = jnp.pad(q_codes.astype(jnp.int32) & mask_b, rows)[:, :, None]
    qm = jnp.pad(q_mask.astype(jnp.float32), rows)[:, :, None]
    dc = (d_codes.astype(jnp.int32) & mask_b).T
    bits_arr = jnp.full((1, 1), bits, jnp.int32)
    mq_p = qc.shape[1]
    out = pl.pallas_call(
        _hamming_kernel,
        grid=(n // block_docs, b),
        in_specs=[
            pl.BlockSpec((1, 1), lambda j, i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, mq_p, 1), lambda j, i: (i, 0, 0),
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
    )(bits_arr, qc, qm, dc, d_mask.astype(jnp.float32).T)
    return out[:, 0, :]
