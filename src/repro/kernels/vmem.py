"""Shared VMEM-budget and tiling accounting for the Pallas kernels.

Every kernel in this package streams fixed-size blocks through VMEM.
Each kernel module exposes a ``*_vmem_bytes`` function computing its
per-grid-step footprint from the same accounting the static verifier
(``repro.analysis.pallas_check``) applies to the traced jaxpr:

    footprint = DOUBLE_BUFFER * (sum of VMEM in/out block bytes)
              + sum of in-kernel non-view temporaries

(block operands are double-buffered by the Pallas pipeline; SMEM
scalars are excluded). The kernels' own functions price every array at
its padded on-chip size (``tile_bytes``). The entry points validate this
*eagerly* at trace time — a tile that cannot fit raises ``ValueError``
carrying the computed footprint instead of failing opaquely inside
Mosaic — and the scan engine's tile picker (``core/scan._kernel_tile``)
consults the same functions to shrink tiles until they fit.

The TPU compiler also requires that the last two dims of every block be
divisible by (8, 128), or equal the array's own dims;
``check_lane_tile`` enforces the lane half of that rule for the kernels
whose doc tile is a lane dim.
"""
from __future__ import annotations

MiB = 2 ** 20

# The scoped-VMEM limit Mosaic gives a kernel by default on TPU v5e (the
# core has 128 MiB of VMEM; kernels get 16 MiB unless their compiler
# params raise it). No kernel here raises it: each stays under this.
VMEM_BUDGET_BYTES = 16 * MiB

# Pallas pipelines block operands: while grid step i computes, step
# i+1's blocks are prefetched — every block buffer exists twice.
DOUBLE_BUFFER = 2

# An on-chip (sublane, lane) tile holds 8 rows of 32-bit values, 16 of
# 16-bit, 32 of 8-bit; 128 lanes in every case.
LANES = 128
SUBLANE_BYTES = 32

__all__ = ["DOUBLE_BUFFER", "LANES", "MiB", "VMEM_BUDGET_BYTES",
           "check_divisible", "check_lane_tile", "check_vmem", "fits",
           "pad_rows", "tile_bytes"]


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def pad_rows(n: int) -> int:
    """Rows of 32-bit values padded to whole 8-row sublane tiles."""
    return _round_up(n, SUBLANE_BYTES // 4)


def tile_bytes(shape, itemsize: int) -> int:
    """Bytes of an array in VMEM, its last two dims padded to the
    (sublane, lane) tile of its dtype."""
    shape = tuple(shape)
    if len(shape) == 1:
        shape = (1,) + shape
    lead = 1
    for d in shape[:-2]:
        lead *= d
    rows = _round_up(shape[-2], SUBLANE_BYTES // itemsize)
    return lead * rows * _round_up(shape[-1], LANES) * itemsize


def fits(footprint_bytes: int, budget: int = VMEM_BUDGET_BYTES) -> bool:
    return footprint_bytes <= budget


def check_divisible(n: int, block_docs: int, *, kernel: str,
                    axis: str = "N") -> None:
    """The grid contract: the streamed axis must tile exactly."""
    if block_docs <= 0:
        raise ValueError(
            f"{kernel}: block_docs must be positive, got {block_docs}")
    if n % block_docs:
        raise ValueError(
            f"{kernel}: {axis}={n} is not divisible by "
            f"block_docs={block_docs} — the grid would drop the last "
            f"{n % block_docs} row(s); pad the operand (kernels/ops.py "
            f"does) or pick a divisor tile")


def check_lane_tile(n: int, tile: int, *, kernel: str) -> None:
    """A tile on the lane dim must be a multiple of 128 or the whole dim
    — the TPU compiler refuses any other block."""
    if tile != n and tile % LANES:
        raise ValueError(
            f"{kernel}: doc tile {tile} of N={n} lies on the lane dim, so "
            f"it must be a multiple of {LANES} or all of N — the TPU "
            f"compiler refuses this block (core/scan._kernel_tile picks "
            f"legal tiles)")


def check_vmem(footprint_bytes: int, *, kernel: str, detail: str,
               budget: int = VMEM_BUDGET_BYTES) -> None:
    """Raise if a kernel's per-grid-step footprint exceeds the budget."""
    if not fits(footprint_bytes, budget):
        raise ValueError(
            f"{kernel}: per-grid-step VMEM footprint "
            f"{footprint_bytes / MiB:.2f} MiB ({detail}) exceeds the "
            f"{budget / MiB:.0f} MiB budget — shrink block_docs (the "
            f"scan engine's _kernel_tile does this automatically) or "
            f"the table width")
