"""Static Pallas kernel verifier: VMEM, tiling and dtype contracts.

Every ``pl.pallas_call`` in ``src/repro/kernels/`` encodes hardware
contracts that used to live in docstrings and bare asserts: the ADC
one-hot tile "fits in VMEM for K <= 512", the corpus axis "must divide
by block_docs", the output "is f32". This module checks them
*statically* — no TPU, no Mosaic lowering — for every registered kernel
geometry (``kernel_sites``: the manifest trace geometry, the
serving-scale geometry, and the documented envelope), and for planted
test fixtures. It does not check the TPU's (8, 128) block tiling, and it
passed kernels the TPU compiler refused: ``tests/test_tpu_compile.py``
compiles them for a described chip.

Capture is two-pass and backend-free:

  1. ``pl.pallas_call`` is temporarily replaced by a shim that records
     each call's grid, BlockSpecs (block shape, index map, memory
     space), out_shape and operand avals, then returns zeros of the
     declared out_shape; the entry point runs under ``jax.eval_shape``
     so nothing executes.
  2. The unpatched entry point is traced with ``jax.make_jaxpr``; each
     ``pallas_call`` equation's kernel jaxpr is walked for in-kernel
     temporaries (the one-hot expansion, similarity buffers — the part
     BlockSpecs alone cannot see). The two passes pair in call order.

Rules (each finding anchors at the kernel function's def site):

  PAL01  VMEM overflow — per-grid-step footprint
         ``DOUBLE_BUFFER * sum(VMEM block bytes) + sum(non-view kernel
         temporaries)`` exceeds ``kernels.vmem.VMEM_BUDGET_BYTES``.
         SMEM blocks are excluded from the VMEM sum.
  PAL02  tiling — an operand/output dimension is not divisible by its
         BlockSpec block size (the grid would drop trailing rows).
  PAL03  coverage — enumerating the grid, some output block is never
         written or is written more than once (racy/partial output).
  PAL04  dtype — an output dtype differs from the site's declared
         contract (e.g. a kernel silently accumulating in bf16).

``tools/jaxlint.py --pallas`` runs every registered site and fails CI
on any finding.
"""
from __future__ import annotations

import dataclasses
import itertools
from collections import Counter
from pathlib import Path
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.analysis.jaxpr_budget import VIEW_PRIMS, iter_jaxprs
from repro.analysis.lintcore import Finding
from repro.kernels import vmem

__all__ = [
    "BlockInfo",
    "CapturedCall",
    "KernelSite",
    "capture_calls",
    "check_all",
    "check_site",
    "kernel_sites",
]

# grid sizes beyond this are spot-checked per-axis instead of fully
# enumerated for PAL03 (registered sites are far below it)
_MAX_GRID_ENUM = 1 << 16

# kernel-jaxpr primitives that do not allocate a new VMEM temporary:
# relayouts plus ref access (get/swap read/write the block buffers that
# the BlockSpec sum already prices)
_KERNEL_FREE_PRIMS = VIEW_PRIMS | {"get", "swap", "broadcast_in_dim"}


@dataclasses.dataclass(frozen=True)
class BlockInfo:
    """One BlockSpec resolved against its operand/output aval."""

    block_shape: Tuple[int, ...]
    array_shape: Tuple[int, ...]
    dtype: str
    memory_space: str
    index_map: Optional[Callable]

    @property
    def is_smem(self) -> bool:
        return "smem" in self.memory_space.lower()

    @property
    def block_bytes(self) -> int:
        n = int(np.prod([d or 1 for d in self.block_shape],
                        dtype=np.int64)) if self.block_shape else 1
        return n * np.dtype(self.dtype).itemsize


@dataclasses.dataclass(frozen=True)
class CapturedCall:
    """One pl.pallas_call site: specs from the shim, temporaries from
    the jaxpr pass (``kernel_tmp_bytes``)."""

    kernel_name: str
    path: str
    line: int
    grid: Tuple[int, ...]
    in_blocks: Tuple[BlockInfo, ...]
    out_blocks: Tuple[BlockInfo, ...]
    kernel_tmp_bytes: int = 0

    def vmem_bytes(self) -> int:
        blocks = sum(b.block_bytes
                     for b in self.in_blocks + self.out_blocks
                     if not b.is_smem)
        return vmem.DOUBLE_BUFFER * blocks + self.kernel_tmp_bytes


@dataclasses.dataclass(frozen=True)
class KernelSite:
    """One registered kernel geometry to verify.

    ``build()`` returns ``(fn, args)`` with ``jax.ShapeDtypeStruct``
    args — the same symbolic-trace convention as the budget manifests.
    ``out_dtypes`` is the declared output dtype contract.
    """

    name: str
    build: Callable[[], Tuple[Callable, tuple]]
    out_dtypes: Tuple[str, ...]
    notes: str = ""


def _as_tuple(x) -> tuple:
    if x is None:
        return ()
    return tuple(x) if isinstance(x, (tuple, list)) else (x,)


def _block_info(spec, operand) -> BlockInfo:
    shape = tuple(getattr(operand, "shape", ()))
    dtype = np.dtype(getattr(operand, "dtype", np.float32)).name
    if spec is None:
        return BlockInfo(shape, shape, dtype, "any", None)
    bs = tuple(getattr(spec, "block_shape", None) or shape)
    return BlockInfo(bs, shape, dtype,
                     str(getattr(spec, "memory_space", "") or ""),
                     getattr(spec, "index_map", None))


def capture_calls(fn, args) -> List[CapturedCall]:
    """Run both capture passes on one entry point; see module docstring."""
    records: List[dict] = []
    real = pl.pallas_call

    def shim(kernel, *, out_shape, grid=None, in_specs=None,
             out_specs=None, **_kw):
        def runner(*operands):
            ops = tuple(jax.ShapeDtypeStruct(o.shape, o.dtype)
                        for o in operands)
            outs = _as_tuple(out_shape)
            records.append({
                "kernel": kernel,
                "grid": _as_tuple(grid),
                "in_blocks": tuple(
                    _block_info(s, o)
                    for s, o in zip(_as_tuple(in_specs) or
                                    (None,) * len(ops), ops)),
                "out_blocks": tuple(
                    _block_info(s, o)
                    for s, o in zip(_as_tuple(out_specs) or
                                    (None,) * len(outs), outs)),
            })
            zeros = jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), out_shape)
            return zeros
        return runner

    # the kernels are @jax.jit entry points: each pass must re-trace, or
    # the shim pass's cached (pallas-free) trace would be served to the
    # jaxpr pass and vice versa
    jax.clear_caches()
    pl.pallas_call = shim
    try:
        jax.eval_shape(fn, *args)
    finally:
        pl.pallas_call = real

    # pass 2: the real trace, for in-kernel temporaries
    jax.clear_caches()
    tmp_bytes: List[int] = []
    closed = jax.make_jaxpr(fn)(*args)
    for j in iter_jaxprs(closed.jaxpr):
        for eqn in j.eqns:
            if eqn.primitive.name != "pallas_call":
                continue
            total = 0
            kernel_jaxpr = eqn.params.get("jaxpr")
            if kernel_jaxpr is not None:
                for kj in iter_jaxprs(getattr(kernel_jaxpr, "jaxpr",
                                              kernel_jaxpr)):
                    for keqn in kj.eqns:
                        if keqn.primitive.name in _KERNEL_FREE_PRIMS:
                            continue
                        for v in keqn.outvars:
                            aval = getattr(v, "aval", None)
                            shape = getattr(aval, "shape", None)
                            dtype = getattr(aval, "dtype", None)
                            if shape is None or dtype is None:
                                continue
                            n = int(np.prod(shape, dtype=np.int64)) \
                                if len(shape) else 1
                            total += n * np.dtype(dtype).itemsize
            tmp_bytes.append(total)

    if len(tmp_bytes) != len(records):          # pragma: no cover
        tmp_bytes = tmp_bytes[:len(records)] + \
            [0] * (len(records) - len(tmp_bytes))

    out: List[CapturedCall] = []
    for rec, tmp in zip(records, tmp_bytes):
        kernel = rec["kernel"]
        code = getattr(kernel, "__code__", None)
        path = getattr(code, "co_filename", "<unknown>")
        try:
            path = str(Path(path).resolve().relative_to(Path.cwd()))
        except ValueError:
            pass
        out.append(CapturedCall(
            kernel_name=getattr(kernel, "__name__", "<kernel>"),
            path=path,
            line=getattr(code, "co_firstlineno", 1),
            grid=rec["grid"],
            in_blocks=rec["in_blocks"],
            out_blocks=rec["out_blocks"],
            kernel_tmp_bytes=tmp,
        ))
    return out


# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------

def _check_vmem(call: CapturedCall, site: str,
                budget: int) -> List[Finding]:
    total = call.vmem_bytes()
    if total <= budget:
        return []
    blocks = total - call.kernel_tmp_bytes
    return [Finding(
        call.path, call.line, "PAL01",
        f"[{site}] {call.kernel_name}: per-grid-step VMEM footprint "
        f"{total / vmem.MiB:.2f} MiB (blocks x{vmem.DOUBLE_BUFFER} = "
        f"{blocks / vmem.MiB:.2f} MiB + kernel temporaries "
        f"{call.kernel_tmp_bytes / vmem.MiB:.2f} MiB) exceeds the "
        f"{budget / vmem.MiB:.0f} MiB budget")]


def _check_divisibility(call: CapturedCall, site: str) -> List[Finding]:
    out: List[Finding] = []
    for kind, blocks in (("operand", call.in_blocks),
                         ("output", call.out_blocks)):
        for idx, b in enumerate(blocks):
            if b.is_smem or len(b.block_shape) != len(b.array_shape):
                continue
            for d, (arr, blk) in enumerate(zip(b.array_shape,
                                               b.block_shape)):
                blk = blk or 1
                if blk and arr % blk:
                    out.append(Finding(
                        call.path, call.line, "PAL02",
                        f"[{site}] {call.kernel_name}: {kind} {idx} dim "
                        f"{d} has size {arr}, not divisible by block "
                        f"{blk} — the grid drops the trailing "
                        f"{arr % blk} row(s)"))
    return out


def _check_coverage(call: CapturedCall, site: str) -> List[Finding]:
    out: List[Finding] = []
    grid = call.grid
    if not grid:
        return out
    n_steps = int(np.prod(grid, dtype=np.int64))
    if n_steps > _MAX_GRID_ENUM:
        return out                               # registered sites are small
    steps = list(itertools.product(*[range(g) for g in grid]))
    for idx, b in enumerate(call.out_blocks):
        if b.index_map is None or len(b.block_shape) != len(b.array_shape):
            continue
        want = set(itertools.product(*[
            range(max(1, arr // (blk or 1)))
            for arr, blk in zip(b.array_shape, b.block_shape)]))
        seen = Counter(tuple(int(c) for c in _as_tuple(b.index_map(*s)))
                       for s in steps)
        missing = want - set(seen)
        multi = {c: n for c, n in seen.items() if c in want and n > 1}
        stray = set(seen) - want
        if missing:
            ex = sorted(missing)[:3]
            out.append(Finding(
                call.path, call.line, "PAL03",
                f"[{site}] {call.kernel_name}: output {idx} has "
                f"{len(missing)} block(s) never written (e.g. {ex}) — "
                f"those regions hold uninitialized memory"))
        if multi:
            c, n = sorted(multi.items())[0]
            out.append(Finding(
                call.path, call.line, "PAL03",
                f"[{site}] {call.kernel_name}: output {idx} block {c} "
                f"written {n} times ({len(multi)} block(s) multi-written)"
                f" — last-write-wins is order-dependent"))
        if stray:
            out.append(Finding(
                call.path, call.line, "PAL03",
                f"[{site}] {call.kernel_name}: output {idx} index map "
                f"addresses {len(stray)} block(s) outside the array "
                f"(e.g. {sorted(stray)[:3]})"))
    return out


def _check_dtypes(call: CapturedCall, site: str,
                  want: Tuple[str, ...]) -> List[Finding]:
    got = tuple(b.dtype for b in call.out_blocks)
    want_n = tuple(np.dtype(d).name for d in want)
    if got == want_n:
        return []
    return [Finding(
        call.path, call.line, "PAL04",
        f"[{site}] {call.kernel_name}: output dtypes {got} != declared "
        f"contract {want_n}")]


def check_site(site: KernelSite, *,
               budget: int = vmem.VMEM_BUDGET_BYTES) -> List[Finding]:
    """All findings for one registered kernel geometry."""
    fn, args = site.build()
    findings: List[Finding] = []
    for call in capture_calls(fn, args):
        findings += _check_vmem(call, site.name, budget)
        findings += _check_divisibility(call, site.name)
        findings += _check_coverage(call, site.name)
        findings += _check_dtypes(call, site.name, site.out_dtypes)
    return findings


def check_all(sites: Optional[Sequence[KernelSite]] = None, *,
              budget: int = vmem.VMEM_BUDGET_BYTES) -> List[Finding]:
    out: List[Finding] = []
    for site in (sites if sites is not None else kernel_sites()):
        out += check_site(site, budget=budget)
    return out


# ---------------------------------------------------------------------------
# The repo registry: every production kernel at its real geometries
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _qmaxsim_site(name: str, *, b: int, mq: int, k: int, md: int,
                  block: int, notes: str = "") -> KernelSite:
    """One ADC-kernel geometry; ``block`` is the scan block (the pallas
    call scores one scan block per invocation), the inner doc tile is
    picked exactly as core/scan.py picks it (VMEM-aware)."""
    def build():
        from repro.core.scan import _kernel_tile
        from repro.kernels import quantized_maxsim as qk
        tile = _kernel_tile(
            block, 256,
            lambda t: vmem.fits(qk.qmaxsim_vmem_bytes(t, mq, k, md)),
            lane=True)

        def fn(table, qm, codes, dm):
            return qk.quantized_maxsim_pallas(table, qm, codes, dm,
                                              block_docs=tile)
        return fn, (_sds((b, mq, k), jnp.float32),
                    _sds((b, mq), jnp.float32),
                    _sds((block, md), jnp.int32),
                    _sds((block, md), jnp.float32))
    return KernelSite(name, build, ("float32",), notes)


def _maxsim_site(name: str, *, b: int, mq: int, md: int, d: int,
                 block: int, notes: str = "") -> KernelSite:
    def build():
        from repro.core.scan import _kernel_tile
        from repro.kernels import maxsim as mk
        tile = _kernel_tile(
            block, 16,
            lambda t: vmem.fits(mk.maxsim_vmem_bytes(t, mq, md, d)))

        def fn(q, qm, docs, dm):
            return mk.maxsim_pallas(q, qm, docs, dm, block_docs=tile)
        return fn, (_sds((b, mq, d), jnp.float32),
                    _sds((b, mq), jnp.float32),
                    _sds((block, md, d), jnp.float32),
                    _sds((block, md), jnp.float32))
    return KernelSite(name, build, ("float32",), notes)


def _hamming_site(name: str, *, b: int, mq: int, md: int,
                  block: int, notes: str = "") -> KernelSite:
    def build():
        from repro.core.scan import _kernel_tile
        from repro.kernels import hamming as hk
        tile = _kernel_tile(
            block, 256,
            lambda t: vmem.fits(hk.hamming_vmem_bytes(t, mq, md)),
            lane=True)

        def fn(qc, qm, dc, dm):
            return hk.hamming_maxsim_pallas(qc, qm, dc, dm, bits=8,
                                            block_docs=tile)
        return fn, (_sds((b, mq), jnp.int32),
                    _sds((b, mq), jnp.float32),
                    _sds((block, md), jnp.int32),
                    _sds((block, md), jnp.float32))
    return KernelSite(name, build, ("float32",), notes)


def _kmeans_site(name: str, *, n: int, k: int, d: int, block_n: int,
                 notes: str = "") -> KernelSite:
    def build():
        from repro.kernels import kmeans_assign as ka

        def fn(x, c):
            return ka.kmeans_assign_pallas(x, c, block_n=block_n)
        return fn, (_sds((n, d), jnp.float32), _sds((k, d), jnp.float32))
    return KernelSite(name, build, ("int32",), notes)


_SITES: Tuple[KernelSite, ...] = (
    _qmaxsim_site("qmaxsim_manifest", b=8, mq=8, k=256, md=16, block=256,
                  notes="the budget manifests' trace geometry"),
    _qmaxsim_site("qmaxsim_serving", b=8, mq=32, k=256, md=128, block=256,
                  notes="serving-scale geometry (ladder max batch)"),
    _qmaxsim_site("qmaxsim_k512", b=8, mq=32, k=512, md=128, block=256,
                  notes="the docstring's K<=512 envelope — the formerly "
                        "unchecked bound; the VMEM-aware tile picker "
                        "must shrink the doc tile to fit"),
    _maxsim_site("maxsim_manifest", b=8, mq=8, md=16, d=16, block=256),
    _maxsim_site("maxsim_serving", b=8, mq=32, md=64, d=128, block=256,
                 notes="the docstring's worked VMEM example"),
    _hamming_site("hamming_manifest", b=8, mq=8, md=16, block=256),
    _hamming_site("hamming_serving", b=8, mq=32, md=128, block=256),
    _kmeans_site("kmeans_assign_default", n=1024, k=256, d=128,
                 block_n=256),
    _kmeans_site("kmeans_assign_k512", n=1024, k=512, d=128, block_n=256,
                 notes="codebook at its documented 512x128 ceiling"),
)


def kernel_sites() -> Tuple[KernelSite, ...]:
    """Every registered production-kernel geometry (stable order)."""
    return _SITES
