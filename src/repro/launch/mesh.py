"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — "pod" is an
additional pure-DP axis across the inter-pod DCN/ICI links.

Defined as functions (never module-level constants) so importing this
module does not touch jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax init,
while tests/benchmarks see the real single CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes, *, devices=None) -> Mesh:
    """`jax.make_mesh` with every axis `AxisType.Auto`.

    The repo's sharding rules (`dist/sharding.py`) and its
    `with_sharding_constraint` calls are written for Auto axes; JAX's
    own default is Explicit, under which gathers on sharded state need
    an explicit `out_sharding`. Every mesh the program builds goes
    through here.
    """
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape=(1, 1), axes=("data", "model")) -> Mesh:
    """Small mesh over whatever host devices exist (tests)."""
    return make_mesh(shape, axes)


# TPU v5e hardware constants (roofline §Roofline)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (per-chip effective)
HBM_BYTES = 16 * 2 ** 30        # 16 GiB per chip
