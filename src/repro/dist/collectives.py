"""Hand-scheduled collectives.

`ring_allgather_matmul` overlaps an all-gather of the weight shards with
the partial matmuls that consume them (the classic ring schedule: at step
i every device multiplies against the weight block it currently holds and
simultaneously passes it to its left neighbour). On TPU the jnp body is
replaced by the Pallas ring-DMA kernel (see /opt guides "Ring
Collectives"); this shard_map + ppermute formulation is the portable
reference schedule that XLA lowers to collective-permute, and is what the
multi-device CPU tests exercise.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def ring_allgather_matmul(mesh: Mesh, axis_name: str):
    """Build f(x, w) = x @ w with a ring-pipelined weight all-gather.

    x (M, K) is sharded over rows, w (K, N) over columns of `axis_name`;
    each of the `n` steps computes one (M/n, N/n) output block while the
    w block moves one hop around the ring, so no device ever materialises
    the full weight. Falls back to a plain matmul when M or N don't tile
    over the axis.
    """
    n = mesh.shape[axis_name]

    def f(x: jax.Array, w: jax.Array) -> jax.Array:
        m, _ = x.shape
        _, p = w.shape
        if n == 1 or m % n != 0 or p % n != 0:
            return x @ w
        blk_p = p // n
        out_dtype = jnp.result_type(x.dtype, w.dtype)
        # after i hops, device d holds w block (d + i) % n
        shift_left = [((j + 1) % n, j) for j in range(n)]

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(P(axis_name, None), P(None, axis_name)),
                 out_specs=P(axis_name, None))
        def run(x_blk, w_blk):
            my = jax.lax.axis_index(axis_name)

            def step(i, carry):
                out, w_cur = carry
                col = (my + i) % n
                part = (x_blk @ w_cur).astype(out_dtype)
                out = jax.lax.dynamic_update_slice(out, part, (0, col * blk_p))
                w_cur = jax.lax.ppermute(w_cur, axis_name, shift_left)
                return out, w_cur

            # the carry varies over the axis after one step; type it so
            # from the start (shard_map's varying-manual-axes check)
            out0 = jax.lax.pcast(jnp.zeros((x_blk.shape[0], p), out_dtype),
                                 axis_name, to="varying")
            out, _ = jax.lax.fori_loop(0, n, step, (out0, w_blk))
            return out

        return run(x, w)

    return f
