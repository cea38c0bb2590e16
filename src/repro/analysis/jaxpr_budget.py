"""Jaxpr budget analyzer: the memory envelope as a checked contract.

HPC-ColPali's value proposition is that compression keeps the search hot
path inside a fixed envelope: peak scan memory O(B * Mq * block * Md),
corpus-proportional allocations bounded by the code payload itself. PR 5
guaranteed that for exactly one entry point with a hand-written jaxpr
walk; this module generalizes the walk into a library driven by the
declarative manifests in ``repro.analysis.manifests``.

For each manifest the analyzer traces the registered entry point twice —
at corpus size ``n`` and at ``n_alt`` (both multiples of the scan block,
so the traced program structure is identical) — walks the closed jaxpr
including every sub-jaxpr nested in ``pjit`` / ``scan`` / ``while`` /
``cond`` equation params, and classifies every intermediate:

  * **static** (same bytes at both sizes): must fit
    ``max_block_bytes`` — the blocked-scan working set;
  * **N-scaling** (bytes grow with the corpus): the growth per document
    must stay under ``max_bytes_per_doc`` — enough for doc ids, validity
    masks and code payload handling, never enough for an O(N * Mq)
    score matrix or a decoded float corpus.

One exemption: *input views* — chains of ``slice`` / ``squeeze`` /
``reshape`` / ``transpose`` rooted at the traced inputs (e.g. hnsw
slicing one level of its (levels, N, 2m) adjacency) are bounded by the
index structure itself, alias or fuse in XLA, and say nothing about the
compute envelope; they are skipped. ``gather`` is deliberately NOT a
view: the unblocked ``table[:, :, codes]`` expansion is exactly what the
budget exists to catch.

Output dtypes are checked against the manifest (hamming scores stay
int32, doc ids stay int32 — the sentinel contract is dtype-stable).
Tracing is shape-symbolic (``jax.ShapeDtypeStruct``): a 2^20-document
corpus costs no memory to analyze.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, List, Optional, Tuple

import jax
import numpy as np
from jax.extend import core as jax_core

# primitives whose output is a (possibly aliased) relayout of one operand
VIEW_PRIMS = frozenset({"slice", "squeeze", "reshape", "transpose"})

__all__ = [
    "BudgetViolation",
    "analyze_manifest",
    "intermediate_avals",
    "iter_jaxprs",
    "max_intermediate_bytes",
]


@dataclasses.dataclass(frozen=True)
class BudgetViolation:
    """One manifest-contract violation."""

    manifest: str
    kind: str        # "block_bytes" | "n_scaling" | "dtype" | "structure"
    detail: str

    def __str__(self) -> str:
        return f"[{self.manifest}] {self.kind}: {self.detail}"

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def iter_jaxprs(jaxpr) -> Iterable:
    """Yield a jaxpr and every jaxpr nested in its eqn params.

    Descends into pjit (``jaxpr`` param), scan/while/cond (``jaxpr`` /
    ``cond_jaxpr`` / ``body_jaxpr`` / ``branches``) and any other
    primitive carrying Jaxpr or ClosedJaxpr values in its params.
    """
    yield jaxpr
    for eqn in jaxpr.eqns:
        for p in eqn.params.values():
            vals = p if isinstance(p, (tuple, list)) else (p,)
            for v in vals:
                inner = getattr(v, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    yield from iter_jaxprs(inner)      # ClosedJaxpr
                elif hasattr(v, "eqns"):               # bare Jaxpr
                    yield from iter_jaxprs(v)


def _aval_bytes(aval) -> Optional[int]:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return None                                    # tokens etc.
    n = int(np.prod(shape, dtype=np.int64)) if len(shape) else 1
    return n * np.dtype(dtype).itemsize


def intermediate_avals(closed) -> List[Tuple[str, object, bool]]:
    """(primitive_name, out_aval, is_input_view) per eqn output.

    The traversal order is deterministic for a fixed traced program
    structure, so two traces of the same Python code at different corpus
    sizes pair positionally. ``is_input_view`` marks outputs of
    ``VIEW_PRIMS`` chains rooted at jaxpr inputs/constants — exempt from
    the budgets (see module docstring).
    """
    out: List[Tuple[str, object, bool]] = []
    for j in iter_jaxprs(closed.jaxpr):
        views = {id(v) for v in j.invars} | {id(v) for v in j.constvars}
        for eqn in j.eqns:
            is_view = (eqn.primitive.name in VIEW_PRIMS
                       and all(isinstance(x, jax_core.Literal)
                               or id(x) in views for x in eqn.invars))
            for v in eqn.outvars:
                if is_view:
                    views.add(id(v))
                out.append((eqn.primitive.name, v.aval, is_view))
    return out


def max_intermediate_bytes(closed) -> int:
    """Largest single non-view intermediate (PR 5's metric)."""
    worst = 0
    for _prim, aval, is_view in intermediate_avals(closed):
        b = _aval_bytes(aval)
        if b is not None and not is_view:
            worst = max(worst, b)
    return worst


def _fmt(prim: str, aval, nbytes: int) -> str:
    return (f"{prim} -> {getattr(aval, 'str_short', lambda: aval)()} "
            f"({nbytes / 2**20:.1f} MiB)")


def analyze_manifest(manifest) -> List[BudgetViolation]:
    """Check one ``BudgetManifest``; returns violations (empty = clean).

    Traces ``manifest.trace(n)`` at ``manifest.n`` and ``manifest.n_alt``
    and applies the growth classification described in the module
    docstring. If the two traces disagree structurally (different eqn
    count — e.g. a ragged tail block at one size only), a "structure"
    violation is reported and the single-trace fallback rule is applied:
    every intermediate must fit ``max_block_bytes`` OR cost at most
    ``max_bytes_per_doc`` per document.
    """
    name = manifest.name
    out: List[BudgetViolation] = []

    fn_big, args_big = manifest.trace(manifest.n)
    closed_big = jax.make_jaxpr(fn_big)(*args_big)
    fn_small, args_small = manifest.trace(manifest.n_alt)
    closed_small = jax.make_jaxpr(fn_small)(*args_small)

    # -- output dtype contracts ---------------------------------------------
    out_avals = [v.aval for v in closed_big.jaxpr.outvars]
    want = manifest.out_dtypes
    if want is not None:
        got = tuple(np.dtype(getattr(a, "dtype", None)).name
                    for a in out_avals)
        want_names = tuple(np.dtype(d).name for d in want)
        if got != want_names:
            out.append(BudgetViolation(
                name, "dtype",
                f"output dtypes {got} != declared {want_names}"))

    ints_big = intermediate_avals(closed_big)
    ints_small = intermediate_avals(closed_small)
    dn = manifest.n - manifest.n_alt

    if len(ints_big) != len(ints_small):
        out.append(BudgetViolation(
            name, "structure",
            f"trace at n={manifest.n} has {len(ints_big)} intermediates vs "
            f"{len(ints_small)} at n_alt={manifest.n_alt}; growth "
            "classification degraded to the single-trace rule (pick n / "
            "n_alt that keep the traced structure identical)"))
        for prim, aval, is_view in ints_big:
            b = _aval_bytes(aval)
            if b is None or is_view or b <= manifest.max_block_bytes:
                continue
            if b / manifest.n > manifest.max_bytes_per_doc:
                out.append(BudgetViolation(
                    name, "block_bytes",
                    f"{_fmt(prim, aval, b)} exceeds max_block_bytes="
                    f"{manifest.max_block_bytes / 2**20:.0f} MiB and "
                    f"{b / manifest.n:.1f} B/doc > max_bytes_per_doc="
                    f"{manifest.max_bytes_per_doc}"))
        return out

    for (prim, a_big, is_view), (_p2, a_small, _v2) in zip(ints_big,
                                                          ints_small):
        b_big, b_small = _aval_bytes(a_big), _aval_bytes(a_small)
        if b_big is None or b_small is None or is_view:
            continue
        if b_big == b_small:
            # static working set: the blocked-scan envelope
            if b_big > manifest.max_block_bytes:
                out.append(BudgetViolation(
                    name, "block_bytes",
                    f"static intermediate {_fmt(prim, a_big, b_big)} "
                    f"exceeds max_block_bytes="
                    f"{manifest.max_block_bytes / 2**20:.0f} MiB"))
        else:
            per_doc = (b_big - b_small) / dn
            if per_doc > manifest.max_bytes_per_doc:
                out.append(BudgetViolation(
                    name, "n_scaling",
                    f"N-scaling intermediate {_fmt(prim, a_big, b_big)} "
                    f"grows {per_doc:.1f} B/doc > max_bytes_per_doc="
                    f"{manifest.max_bytes_per_doc} (an O(N*Mq) score "
                    "matrix or decoded corpus is sneaking back in)"))
    return out


def report(manifest) -> dict:
    """Machine-readable summary for one manifest (jaxlint --json)."""
    violations = analyze_manifest(manifest)
    fn, args = manifest.trace(manifest.n)
    closed = jax.make_jaxpr(fn)(*args)
    return {
        "manifest": manifest.name,
        "n": manifest.n,
        "max_block_bytes": manifest.max_block_bytes,
        "max_bytes_per_doc": manifest.max_bytes_per_doc,
        "worst_intermediate_bytes": max_intermediate_bytes(closed),
        "n_intermediates": len(intermediate_avals(closed)),
        "violations": [v.to_json() for v in violations],
        "ok": not violations,
    }
