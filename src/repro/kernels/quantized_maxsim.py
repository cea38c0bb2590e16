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
    sim_j    = T_G @ onehot_j                        # (G*Mq, block_docs)

where T_G stacks the tables of a group of G queries: the one-hot of a
(block, patch) is built once and pushed into the MXU once for the whole
group, which streams G*Mq table rows through it. The kernel folds
`sim_j` into the group's running masked max over j < Md, kept in a
(G*Mq, block_docs) VMEM scratch; the query-masked sum over Mq then
leaves one (1, block_docs) score row a query. Every block's last two
dims are (8, 128)-aligned or whole — what the TPU compiler accepts —
and the per-step VMEM is O(K * block_docs) plus O(G * Mq * (K +
block_docs)) for the group, so wide geometries (Md=615, K=512) fit
without shrinking the tile.

G is not a setting: `query_group` derives it from the call's shapes and
`vmem.VMEM_BUDGET_BYTES` (the widest group that fits, then the batch
split evenly over as many groups as that needs, padded by at most a row
a group). At B=1 it is 1, one query a step as before; at the colpali-hpc
top rung (B=64, Mq=32, K=256, Md=615, block 256) it is 32.

Precision: the one-hot is exact in bf16, and the f32 table is split into
three bf16 terms (hi + mid + lo == T exactly), so three bf16 MXU passes
with f32 accumulation return the f32 table entries bit for bit; a
default-precision f32 matmul would round T to bf16.

Grid: (N // block_docs, B / G), query groups innermost: each doc tile
is fetched from HBM once per batch, and the group's (3*G*Mq, K) table
block (the hi rows of its G queries, then the mid rows, then the lo
rows) is what changes between steps. Each score is still exactly
hi[c] + mid[c] + lo[c], summed in that order, whatever G is.
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




def qmaxsim_vmem_bytes(block_docs: int, mq: int, k: int, md: int,
                       g: int = 1) -> int:
    """Per-grid-step VMEM footprint of ``_qmaxsim_kernel`` in bytes, for
    a group of ``g`` queries scored against each one-hot.

    Double-buffered blocks (the group's stacked split tables, its
    q_mask columns, the transposed codes and mask, its score rows), the
    (g*Mq, block_docs) running-max scratch, and the per-patch
    temporaries: the (K, block_docs) iota, compare and bf16 one-hot, the
    (3*g*Mq, block_docs) matmul result, and the group's similarity and
    masked rows.
    """
    tb, rows = vmem.tile_bytes, g * vmem.pad_rows(mq)
    blocks = (tb((3 * rows, k), 2) + tb((g, vmem.pad_rows(mq), 1), 4)
              + 2 * tb((md, block_docs), 4) + tb((g, 1, block_docs), 4))
    acc = tb((rows, block_docs), 4)
    onehot = 2 * tb((k, block_docs), 4) + tb((k, block_docs), 2)
    sims = tb((3 * rows, block_docs), 4) + 2 * tb((rows, block_docs), 4)
    return vmem.DOUBLE_BUFFER * blocks + acc + onehot + sims


def query_group(b: int, mq: int, k: int, md: int, block_docs: int) -> int:
    """Queries scored in one matmul against each (block, patch) one-hot.

    The largest group whose footprint fits ``vmem.VMEM_BUDGET_BYTES``
    sets how many groups the batch needs; the batch is then split into
    that many groups as evenly as it goes, so the padded batch is at
    most one row a group longer than ``b``. At ``b`` = 1 it is 1. If not
    even one query fits, 1 (the call's own check then raises).
    """
    g = b
    while g > 1 and not vmem.fits(
            qmaxsim_vmem_bytes(block_docs, mq, k, md, g)):
        g -= 1
    return -(-b // -(-b // g))


# {batch: widest group} of the calls traced in this process. A served
# search is a compiled program, so the serving loop reads here what its
# trace chose (`serve.onehot_shared_queries`).
_TRACED_GROUPS: dict = {}


def traced_group(b: int) -> int:
    """The widest query group of any call traced at batch ``b`` in this
    process; 0 if none was."""
    return _TRACED_GROUPS.get(b, 0)


def _qmaxsim_kernel(tab_ref, qm_ref, codes_ref, dm_ref, out_ref, acc_ref):
    # tab_ref:   (1, 3*G*Mq, K) bf16 — the hi rows of the group's G
    #            queries, then their mid rows, then their lo rows
    # qm_ref:    (G, Mq, 1) f32 query-token masks, columns
    # codes_ref: (Md, T) int32, docs on lanes
    # dm_ref:    (Md, T) f32 patch mask, same layout
    # out_ref:   (G, 1, T) f32
    # acc_ref:   (G*Mq, T) f32 scratch, the group's running masked max
    tab = tab_ref[0]
    g, mq = qm_ref.shape[0], qm_ref.shape[1]
    rows, k = g * mq, tab.shape[1]
    md, t = codes_ref.shape
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (k, t), 0)
    acc_ref[...] = jnp.full((rows, t), NEG_INF, jnp.float32)

    @pl.loop(0, md)
    def _(j):
        row = codes_ref[pl.ds(j, 1), :]                   # (1, T)
        onehot = (iota_k == row).astype(jnp.bfloat16)     # (K, T)
        s3 = jnp.dot(tab, onehot, preferred_element_type=jnp.float32)
        sim = (s3[:rows] + s3[rows:2 * rows]) + s3[2 * rows:]  # T[:, c]
        sim = jnp.where(dm_ref[pl.ds(j, 1), :] > 0, sim, NEG_INF)
        acc_ref[...] = jnp.maximum(acc_ref[...], sim)

    for i in range(g):
        per_q = acc_ref[i * mq:(i + 1) * mq, :]           # (Mq, T)
        out_ref[i] = jnp.sum(per_q * qm_ref[i], axis=0, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("block_docs", "interpret", "name"))
def quantized_maxsim_pallas(table, q_mask, codes, d_mask, *,
                            block_docs: int = 256, interpret: bool = False,
                            name: str = "quantized_maxsim_pallas"):
    """table (B, Mq, K) f32, q_mask (B, Mq) f32, codes (N, Md) int,
    d_mask (N, Md) f32 -> scores (B, N) f32.  N % block_docs == 0; on
    the chip block_docs is a multiple of 128 or N. `name` is the
    kernel's name in errors and in a profiler trace."""
    b, mq, k = table.shape
    n, md = codes.shape
    vmem.check_divisible(n, block_docs, kernel=name)
    if not interpret:
        vmem.check_lane_tile(n, block_docs, kernel=name)
    g = query_group(b, mq, k, md, block_docs)
    _TRACED_GROUPS[b] = max(g, _TRACED_GROUPS.get(b, 0))
    vmem.check_vmem(
        qmaxsim_vmem_bytes(block_docs, mq, k, md, g), kernel=name,
        detail=f"block_docs={block_docs}, Mq={mq}, K={k}, Md={md}, "
               f"{g} queries a group; the one-hot tile is "
               f"({k}, {block_docs})")
    mq_p, groups = vmem.pad_rows(mq), -(-b // g)
    pad = ((0, groups * g - b), (0, mq_p - mq))
    tab3 = jnp.concatenate(
        [x.reshape(groups, g * mq_p, k) for x in split_bf16(
            jnp.pad(table.astype(jnp.float32), pad + ((0, 0),)))],
        axis=1)                                     # (groups, 3*G*Mq_p, K)
    qm = jnp.pad(q_mask.astype(jnp.float32), pad)[:, :, None]
    with jax.named_scope("kernel.layout"):      # docs on lanes
        codes_t = codes.astype(jnp.int32).T
        mask_t = d_mask.astype(jnp.float32).T
    out = pl.pallas_call(
        _qmaxsim_kernel,
        grid=(n // block_docs, groups),
        in_specs=[
            pl.BlockSpec((1, 3 * g * mq_p, k), lambda j, i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((g, mq_p, 1), lambda j, i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((md, block_docs), lambda j, i: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((md, block_docs), lambda j, i: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((g, 1, block_docs), lambda j, i: (i, 0, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((groups * g, 1, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((g * mq_p, block_docs), jnp.float32)],
        interpret=interpret,
        name=name,
    )(tab3, qm, codes_t, mask_t)
    return out[:b, 0, :]
