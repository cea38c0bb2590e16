"""Tests for the static Pallas kernel verifier (repro.analysis.pallas_check).

The registered production geometries must verify clean, and each rule
(PAL01 VMEM overflow, PAL02 tiling divisibility, PAL03 output-block
coverage, PAL04 dtype contract) is proven live on a planted kernel
defined in THIS file — every finding must anchor at the planted
kernel's def line here, exact (file, rule).

Also covers the runtime half of the contract (kernels/vmem.py): the
kernels' bare asserts became ValueErrors carrying the computed VMEM
footprint, and the scan engine's tile picker shrinks the doc tile until
the footprint fits — the docstring's formerly unchecked "K <= 512 keeps
it in VMEM" envelope.
"""
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from repro.analysis.pallas_check import (KernelSite, capture_calls,
                                         check_all, check_site,
                                         kernel_sites)
from repro.kernels import quantized_maxsim as qk
from repro.kernels import vmem

sds = jax.ShapeDtypeStruct
HERE = Path(__file__).name


# --- the repo registry verifies clean -------------------------------------

def test_registered_kernel_sites_are_clean():
    sites = kernel_sites()
    assert {s.name for s in sites} >= {
        "qmaxsim_manifest", "qmaxsim_serving", "qmaxsim_k512",
        "qmaxsim_colpali_b64", "maxsim_serving", "hamming_serving", "kmeans_assign_default"}
    assert check_all() == []


def test_capture_sees_blockspecs_and_kernel_temporaries():
    site = next(s for s in kernel_sites() if s.name == "qmaxsim_serving")
    fn, args = site.build()
    calls = capture_calls(fn, args)
    assert len(calls) == 1
    call = calls[0]
    assert call.path.endswith("src/repro/kernels/quantized_maxsim.py")
    assert call.kernel_name == "_qmaxsim_kernel"
    assert call.grid and all(g >= 1 for g in call.grid)
    # codes are scored transposed, (Md, tile): the per-patch one-hot is a
    # (K, tile) bf16 expansion — the jaxpr pass must see at least that
    # much in-kernel VMEM (the part BlockSpecs cannot)
    md, tile = call.in_blocks[2].block_shape
    k = call.in_blocks[0].block_shape[2]
    assert call.kernel_tmp_bytes >= k * tile * 2
    assert tile % vmem.LANES == 0 or tile == call.in_blocks[2].array_shape[1]
    assert call.vmem_bytes() <= vmem.VMEM_BUDGET_BYTES


# --- planted violations: each rule fires at exact (file, rule) ------------

def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _bf16_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...].astype(jnp.bfloat16)


def _site(fn, args, out_dtypes=("float32",), name="planted"):
    return KernelSite(name, lambda: (fn, args), out_dtypes)


def _findings_for(fn, args, **kw):
    return check_site(_site(fn, args, **kw))


def test_pal01_vmem_overflow_fires_here():
    # one (2048, 2048) f32 block in + out = 32 MiB, double-buffered to
    # 64 MiB against the 16 MiB budget
    shape = (2048, 2048)

    def fn(x):
        return pl.pallas_call(
            _copy_kernel,
            out_shape=sds(shape, jnp.float32),
            grid=(1,),
            in_specs=[pl.BlockSpec(shape, lambda i: (0, 0))],
            out_specs=pl.BlockSpec(shape, lambda i: (0, 0)),
        )(x)

    findings = _findings_for(fn, (sds(shape, jnp.float32),))
    assert [f.code for f in findings] == ["PAL01"]
    f = findings[0]
    assert Path(f.path).name == HERE
    assert f.line == _copy_kernel.__code__.co_firstlineno
    assert "VMEM footprint" in f.msg and "MiB" in f.msg


def test_pal02_non_divisible_block_fires_here():
    # 100 rows against an 8-row block: the grid drops 4 trailing rows
    def fn(x):
        return pl.pallas_call(
            _copy_kernel,
            out_shape=sds((100, 8), jnp.float32),
            grid=(12,),
            in_specs=[pl.BlockSpec((8, 8), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 8), lambda i: (i, 0)),
        )(x)

    findings = _findings_for(fn, (sds((100, 8), jnp.float32),))
    assert {f.code for f in findings} == {"PAL02"}
    assert len(findings) == 2          # operand 0 and output 0
    assert all(Path(f.path).name == HERE for f in findings)
    assert "not divisible" in findings[0].msg
    assert "4 row(s)" in findings[0].msg


def test_pal03_uncovered_and_multiwritten_blocks_fire_here():
    # 4 output blocks, but every grid step lands on block (0, 0): three
    # blocks never written, one written four times
    def fn(x):
        return pl.pallas_call(
            _copy_kernel,
            out_shape=sds((64, 8), jnp.float32),
            grid=(4,),
            in_specs=[pl.BlockSpec((16, 8), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((16, 8), lambda i: (0, 0)),
        )(x)

    findings = _findings_for(fn, (sds((64, 8), jnp.float32),))
    assert [f.code for f in findings] == ["PAL03", "PAL03"]
    assert all(Path(f.path).name == HERE for f in findings)
    missing = [f for f in findings if "never written" in f.msg]
    multi = [f for f in findings if "written 4 times" in f.msg]
    assert len(missing) == 1 and "3 block(s)" in missing[0].msg
    assert len(multi) == 1


def test_pal04_output_dtype_contract_fires_here():
    shape = (64, 8)

    def fn(x):
        return pl.pallas_call(
            _bf16_kernel,
            out_shape=sds(shape, jnp.bfloat16),
            grid=(4,),
            in_specs=[pl.BlockSpec((16, 8), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((16, 8), lambda i: (i, 0)),
        )(x)

    findings = _findings_for(fn, (sds(shape, jnp.float32),),
                             out_dtypes=("float32",))
    assert [f.code for f in findings] == ["PAL04"]
    f = findings[0]
    assert Path(f.path).name == HERE
    assert f.line == _bf16_kernel.__code__.co_firstlineno
    assert "bfloat16" in f.msg and "float32" in f.msg


def test_planted_over_vmem_blockspec_rejected_in_registry_shape():
    """Acceptance: the same over-VMEM geometry packaged exactly like a
    registry site is rejected by check_all when passed explicitly."""
    shape = (4096, 1024)

    def fn(x):
        return pl.pallas_call(
            _copy_kernel,
            out_shape=sds(shape, jnp.float32),
            grid=(1,),
            in_specs=[pl.BlockSpec(shape, lambda i: (0, 0))],
            out_specs=pl.BlockSpec(shape, lambda i: (0, 0)),
        )(x)

    site = _site(fn, (sds(shape, jnp.float32),), name="planted_overflow")
    findings = check_all([site] + list(kernel_sites()))
    assert [f.code for f in findings] == ["PAL01"]
    assert "[planted_overflow]" in findings[0].msg


# --- the runtime contract: ValueErrors with computed footprints -----------

def test_qmaxsim_k512_default_tile_overflows_and_raises():
    """At K=512 a doc tile of 4096 lanes (Md=128) needs more VMEM than
    the budget — the entry point must say so instead of silently
    spilling."""
    need = qk.qmaxsim_vmem_bytes(4096, 32, 512, 128)
    assert need > vmem.VMEM_BUDGET_BYTES

    def call():
        return qk.quantized_maxsim_pallas(
            jnp.zeros((8, 32, 512)), jnp.ones((8, 32)),
            jnp.zeros((4096, 128), jnp.int32), jnp.ones((4096, 128)),
            block_docs=4096)
    with pytest.raises(ValueError, match="VMEM footprint") as ei:
        jax.eval_shape(call)
    assert "one-hot tile is (512, 4096)" in str(ei.value)


def test_scan_tile_picker_shrinks_k512_to_fit():
    from repro.core.scan import _kernel_tile
    fits = lambda t: vmem.fits(qk.qmaxsim_vmem_bytes(t, 32, 512, 128))
    tile = _kernel_tile(4096, 4096, fits, lane=True)
    assert tile == 2048
    assert vmem.fits(qk.qmaxsim_vmem_bytes(tile, 32, 512, 128))
    # only lane-legal tiles: multiples of 128, or the whole block
    assert _kernel_tile(256, 256, fits, lane=True) == 256
    assert _kernel_tile(100, 256, fits, lane=True) == 100
    assert _kernel_tile(384, 256, fits, lane=True) == 128
    # and the static verifier agrees: the k512 registry site is clean
    site = next(s for s in kernel_sites() if s.name == "qmaxsim_k512")
    assert check_site(site) == []


def test_check_divisible_is_a_valueerror_not_an_assert():
    with pytest.raises(ValueError, match="quantized_maxsim_pallas"):
        jax.eval_shape(lambda: qk.quantized_maxsim_pallas(
            jnp.zeros((2, 4, 16)), jnp.ones((2, 4)),
            jnp.zeros((100, 8), jnp.int32), jnp.ones((100, 8)),
            block_docs=32))
    with pytest.raises(ValueError, match="block_docs"):
        vmem.check_divisible(64, 0, kernel="k")
