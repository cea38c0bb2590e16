"""Pallas TPU kernel: tiled nearest-centroid assignment (K-Means E-step).

dist(x, c) = ||x||^2 - 2 x.C^T + ||c||^2 ; argmin over K.

The codebook (K, D) <= 512x128x4 = 256 KB stays VMEM-resident across the
whole sweep; points stream in blocks of `block_n` rows, one full-precision
MXU matmul per tile. Distances are formed transposed, (K, block_n), so
the argmin runs down sublanes and the codes leave as a lane-dense
(1, block_n) row of a (1, N) output — a 1-D output block would get a
layout XLA does not give the array. ||c||^2 is folded in-kernel
(recomputed per tile — K*D mults, negligible vs the matmul, avoids a
second input stream).

Grid: (N // block_n,).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import vmem


def kmeans_assign_vmem_bytes(block_n: int, k: int, d: int) -> int:
    """Per-grid-step VMEM footprint of ``_assign_kernel`` in bytes:
    double-buffered blocks (points, codebook, codes out) + the
    (K, block_n) distance/index temporaries and the centroid-norm fold."""
    tb = vmem.tile_bytes
    blocks = tb((block_n, d), 4) + tb((k, d), 4) + tb((1, block_n), 4)
    temps = (tb((k, d), 4) + tb((k, 1), 4) + 4 * tb((k, block_n), 4)
             + 2 * tb((1, block_n), 4))
    return vmem.DOUBLE_BUFFER * blocks + temps


def _assign_kernel(x_ref, c_ref, out_ref):
    # x_ref: (block_n, D); c_ref: (K, D); out_ref: (1, block_n) int32
    x = x_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    c2 = jnp.sum(c * c, axis=-1, keepdims=True)           # (K, 1)
    cx = jax.lax.dot_general(c, x, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    # ||x||^2 is constant per column — argmin unaffected; skip it.
    d = c2 - 2.0 * cx                                     # (K, block_n)
    best = jnp.min(d, axis=0, keepdims=True)
    # lowest index attaining the min: jnp.argmin's tie-breaking
    k_iota = jax.lax.broadcasted_iota(jnp.int32, d.shape, 0)
    out_ref[...] = jnp.min(jnp.where(d == best, k_iota, d.shape[0]),
                           axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_assign_pallas(x, centroids, *, block_n: int = 256,
                         interpret: bool = False):
    """x (N, D), centroids (K, D) -> codes (N,) int32.  N % block_n == 0;
    on the chip block_n is a multiple of 128 or N."""
    n, d = x.shape
    k, _ = centroids.shape
    name = "kmeans_assign_pallas"
    vmem.check_divisible(n, block_n, kernel=name)
    if not interpret:
        vmem.check_lane_tile(n, block_n, kernel=name)
    vmem.check_vmem(
        kmeans_assign_vmem_bytes(block_n, k, d), kernel=name,
        detail=f"block_n={block_n}, K={k}, D={d}; the distance tile is "
               f"({k}, {block_n}) f32")
    out = pl.pallas_call(
        _assign_kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, d), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=interpret,
        name=name,
    )(x.astype(jnp.float32), centroids.astype(jnp.float32))
    return out[0]
