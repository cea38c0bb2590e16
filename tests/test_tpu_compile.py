"""Compile-only checks for the TPU: the main-path kernels and the flat
search at the colpali-hpc widths, for a described (not attached) v5e.

The interpret-mode tests (test_kernels.py) check results; only the TPU
compiler checks what the chip accepts — the (8, 128) block tiling, VMEM,
layouts, partitioning. These tests run that compiler for a `v5e:2x2`
topology described in a fixture, with shapes and no arrays, so they
need no chip. Nothing here executes, so nothing here is a measurement.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports every test file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.colpali_hpc import COLPALI_HPC
from repro.core import pruning
from repro.core import scan as scan_mod
from repro.kernels import hamming as hk
from repro.kernels import kmeans_assign as ka
from repro.kernels import maxsim as mk
from repro.kernels import quantized_maxsim as qk
from repro.kernels import vmem
from repro.retrieval import Query, Retriever

ARCH = COLPALI_HPC.config
ENC, HPC = ARCH.encoder, ARCH.hpc
D, MQ, K = ENC.proj_dim, ENC.query_len, HPC.k
MD_FULL = ENC.n_patches                          # 1024, the rerank rows
MD = pruning.keep_count(MD_FULL, HPC.p)          # codes kept at p=60
B = ARCH.serve_queries                           # the ladder's top rung
BLOCK = HPC.scan_block_docs                      # docs per kernel call
N = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return compiled


def _qfits(mq, k, md):
    return lambda t: vmem.fits(qk.qmaxsim_vmem_bytes(t, mq, k, md))


def test_adc_kernel_compiles_at_colpali_widths(one_chip):
    tile = scan_mod._kernel_tile(BLOCK, 256, _qfits(MQ, K, MD), lane=True)
    _compile(lambda t, qm, c, m: qk.quantized_maxsim_pallas(
        t, qm, c, m, block_docs=tile),
        _sds((B, MQ, K), jnp.float32, one_chip),
        _sds((B, MQ), jnp.float32, one_chip),
        _sds((BLOCK, MD), jnp.int32, one_chip),
        _sds((BLOCK, MD), jnp.float32, one_chip))


@pytest.mark.parametrize("p", [HPC.rerank, ARCH.top_k])
def test_adc_kernel_compiles_for_per_query_rerank(one_chip, p):
    """The facade rerank's layout: (B, P, 1024) unpruned codes per query,
    one kernel call per query (vmapped)."""
    _compile(lambda q, qm, c, m, cb: scan_mod.quantized_maxsim_topk(
        q, qm, c, m, cb, k=min(p, ARCH.top_k),
        scan=scan_mod.ScanConfig(BLOCK, "pallas")),
        _sds((B, MQ, D), jnp.float32, one_chip),
        _sds((B, MQ), jnp.bool_, one_chip),
        _sds((B, p, MD_FULL), jnp.uint8, one_chip),
        _sds((B, p, MD_FULL), jnp.bool_, one_chip),
        _sds((K, D), jnp.float32, one_chip))


def test_hamming_kernel_compiles_at_colpali_widths(one_chip):
    tile = scan_mod._kernel_tile(
        BLOCK, 256, lambda t: vmem.fits(hk.hamming_vmem_bytes(t, MQ, MD)),
        lane=True)
    _compile(lambda q, qm, c, m: hk.hamming_maxsim_pallas(
        q, qm, c, m, bits=HPC.bits, block_docs=tile),
        _sds((B, MQ), jnp.int32, one_chip),
        _sds((B, MQ), jnp.float32, one_chip),
        _sds((BLOCK, MD), jnp.int32, one_chip),
        _sds((BLOCK, MD), jnp.float32, one_chip))


def test_maxsim_kernel_compiles_at_colpali_widths(one_chip):
    tile = scan_mod._kernel_tile(
        BLOCK, 16, lambda t: vmem.fits(mk.maxsim_vmem_bytes(t, MQ, MD, D)))
    _compile(lambda q, qm, d, m: mk.maxsim_pallas(q, qm, d, m,
                                                   block_docs=tile),
             _sds((B, MQ, D), jnp.float32, one_chip),
             _sds((B, MQ), jnp.float32, one_chip),
             _sds((BLOCK, MD, D), jnp.float32, one_chip),
             _sds((BLOCK, MD), jnp.float32, one_chip))


def test_kmeans_assign_kernel_compiles_for_a_page_chunk(one_chip):
    """Quantizing one 64-page chunk of 1024-patch pages."""
    _compile(lambda x, c: ka.kmeans_assign_pallas(x, c),
             _sds((64 * MD_FULL, D), jnp.float32, one_chip),
             _sds((K, D), jnp.float32, one_chip))


def _state_and_query(r, sharding_of, n=N):
    """abstract_state shapes at the colpali widths; rerank rows unpruned."""
    st = r.backend.abstract_state(n=n, md=MD, d=D, k=K)
    st = st._replace(
        rerank_codes=jax.ShapeDtypeStruct((n, MD_FULL), jnp.uint8),
        rerank_mask=jax.ShapeDtypeStruct((n, MD_FULL), jnp.bool_))
    st = jax.tree.map(lambda s: _sds(s.shape, s.dtype, sharding_of(s)), st)
    return st


def _query(sharding):
    return Query(_sds((B, MQ, D), jnp.float32, sharding),
                 _sds((B, MQ), jnp.bool_, sharding),
                 _sds((B, MQ), jnp.float32, sharding))


def test_flat_search_compiles_with_the_kernel(one_chip):
    """One whole Retriever.search of the deployment: 2^20 pages, the
    ladder's top rung, top-k 128 after rerank 32 — the program
    chip_smoke.py serves."""
    r = Retriever(dataclasses.replace(HPC, scan_impl="pallas"))
    st = _state_and_query(r, lambda s: one_chip)
    compiled = _compile(lambda s, q: r.search(s, q, k=ARCH.top_k), st,
                        _query(one_chip))
    mem = compiled.memory_analysis()
    # the index alone: 615 codes + mask, 1024 rerank codes + mask a page
    assert mem.argument_size_in_bytes > N * 2 * (MD + MD_FULL)


def test_sharded_flat_search_compiles_on_four_chips(topo):
    """Retriever.search_sharded over a corpus split across the 2x2 mesh:
    each chip holds a quarter of the index and runs the kernel on it."""
    from repro.dist.sharding import Sharder, is_logical_spec
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("data",), devices=topo.devices)
    r = Retriever(dataclasses.replace(HPC, scan_impl="pallas"))
    n = 1 << 16
    shd = Sharder(mesh)
    st = _state_and_query(r, lambda s: None, n=n)
    st = jax.tree.map(
        lambda spec, s: _sds(s.shape, s.dtype, shd.named(tuple(spec),
                                                         s.shape)),
        r.backend.shard_specs(st), st, is_leaf=is_logical_spec)
    compiled = _compile(
        lambda s, q: r.search_sharded(s, q, k=ARCH.top_k, mesh=mesh), st,
        _query(NamedSharding(mesh, P())))
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < n * 2 * (MD + MD_FULL) / 3
