"""Multi-device distribution tests (subprocess with virtual CPU devices —
the main process keeps its single real device, per the assignment)."""
import pytest

from tests.conftest import run_subprocess


@pytest.mark.slow
def test_sharded_kmeans_matches_local():
    """The full v2 trainer (k-means++ seeds, Lloyd + empty-cluster repair,
    best-iterate, restarts) sharded over 8 devices must match the
    single-host `kmeans_fit` within psum tolerance."""
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import distributed as D, quantization as quant

from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (512, 8))
cfg = quant.KMeansConfig(k=16, iters=10, n_restarts=2)
c_sh, hist_sh = D.sharded_kmeans_fit(mesh, key, x, cfg)
c_ref, hist_ref = quant.kmeans_fit(key, x, cfg)
np.testing.assert_allclose(np.asarray(c_sh), np.asarray(c_ref), atol=1e-4)
np.testing.assert_allclose(np.asarray(hist_sh), np.asarray(hist_ref), atol=1e-4)
codes_sh = D.sharded_quantize(mesh, x.reshape(64, 8, 8), c_ref, jnp.uint8)
codes_ref = quant.quantize(x.reshape(64, 8, 8), c_ref)
assert bool(jnp.all(codes_sh == codes_ref))
print("SHARDED_KMEANS_OK")
""")
    assert "SHARDED_KMEANS_OK" in out


@pytest.mark.slow
def test_sharded_retriever_build_matches_local():
    """Retriever.build(mesh=...) across 8 devices: codebook within psum
    tolerance of the single-host build, identical search answers."""
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.data import synthetic
from repro.retrieval import Corpus, HPCConfig, Query, Retriever

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
spec = synthetic.CorpusSpec(n_docs=128, n_queries=8, n_patches=8,
                            n_q_patches=4, dim=16, n_topics=4)
data = synthetic.make_retrieval_corpus(jax.random.PRNGKey(1), spec)
cfg = HPCConfig(k=32, p=60.0, backend="flat", prune_side="doc",
                kmeans_iters=8, rerank=12)
r = Retriever(cfg)
corpus = Corpus(data.doc_patches, data.doc_mask, data.doc_salience)
st_mesh = r.build(jax.random.PRNGKey(2), corpus, mesh=mesh)
st_local = r.build(jax.random.PRNGKey(2), corpus)
np.testing.assert_allclose(np.asarray(st_mesh.codebook),
                           np.asarray(st_local.codebook), atol=1e-4)
q = Query(data.query_patches, data.query_mask, data.query_salience)
s_m, i_m = r.search(st_mesh, q, k=5)
s_l, i_l = r.search(st_local, q, k=5)
np.testing.assert_allclose(np.asarray(s_m), np.asarray(s_l), atol=1e-4)
np.testing.assert_array_equal(np.asarray(i_m), np.asarray(i_l))
print("SHARDED_BUILD_OK")
""")
    assert "SHARDED_BUILD_OK" in out


@pytest.mark.slow
def test_ring_allgather_matmul():
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.dist.collectives import ring_allgather_matmul

from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("model",))
key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (16, 8))
w = jax.random.normal(jax.random.PRNGKey(1), (8, 12))
f = ring_allgather_matmul(mesh, "model")
y = f(x, w)
np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w), atol=1e-4)
print("RING_OK")
""")
    assert "RING_OK" in out


@pytest.mark.slow
def test_pipeline_parallel_matches_sequential():
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.train.loop import make_pipelined_fn

from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("pipe",))
key = jax.random.PRNGKey(0)
n_stages, mb, n_micro, d = 4, 4, 8, 16
ws = jax.random.normal(key, (n_stages, d, d)) / jnp.sqrt(d)

def stage_fn(sp, x):
    return jnp.tanh(x @ sp["w"])

x = jax.random.normal(jax.random.PRNGKey(1), (n_micro * mb, d))
piped = make_pipelined_fn(mesh, stage_fn, n_microbatches=n_micro)
y = piped({"w": ws}, x)

ref = x
for i in range(n_stages):
    ref = jnp.tanh(ref @ ws[i])
np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-4)
print("PIPE_OK")
""")
    assert "PIPE_OK" in out


@pytest.mark.slow
def test_elastic_restore_across_meshes(tmp_path):
    out = run_subprocess(f"""
import jax, jax.numpy as jnp, numpy as np
from repro.ckpt import checkpoint as ck
from repro.train import elastic
from repro.dist.sharding import Sharder

tree = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
specs = {{"w": ("batch", "mlp")}}
ck.save("{tmp_path}", 3, tree)

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
got = elastic.restore_elastic("{tmp_path}", jax.tree.map(jnp.zeros_like, tree), specs, mesh)
step, restored = got
assert step == 3
np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))
shard_shape = restored["w"].sharding.shard_shape(restored["w"].shape)
assert shard_shape == (4, 2), shard_shape
print("ELASTIC_OK")
""")
    assert "ELASTIC_OK" in out


@pytest.mark.slow
def test_dryrun_smoke_cells_compile_on_small_mesh():
    """The dry-run machinery itself (build_cell + lower + compile) on a
    small virtual mesh with smoke configs — one cell per family."""
    out = run_subprocess("""
import jax
from jax.sharding import Mesh
from repro.configs import registry
from repro.launch import cells as cm
import numpy as np

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
for arch, shape in [("qwen2-1.5b", "train_4k"), ("llama4-scout-17b-a16e", "decode_32k"),
                    ("pna", "molecule"), ("dlrm-mlperf", "serve_p99"),
                    ("dien", "retrieval_cand"), ("colpali-hpc", "serve_query")]:
    spec = registry.get(arch)
    cell = [c for c in spec.shapes if c.name == shape][0]
    with mesh:
        built = cm.build_cell(spec, cell, mesh, smoke=True)
        if built.in_shardings is None:
            jitted = built.fn
        else:
            jitted = jax.jit(built.fn, in_shardings=built.in_shardings,
                             out_shardings=built.out_shardings,
                             donate_argnums=built.donate_argnums)
        compiled = jitted.lower(*built.args).compile()
        assert compiled.memory_analysis() is not None
    print("OK", arch, shape)
print("DRYRUN_SMOKE_OK")
""", n_devices=8, timeout=900)
    assert "DRYRUN_SMOKE_OK" in out


@pytest.mark.slow
def test_grouped_moe_ep_matches_unsharded():
    """moe-2 (EXPERIMENTS.md §Perf): the grouped expert-parallel dispatch
    must be numerically exact under a real sharded mesh (g=4) vs the
    unsharded reference (g=1), in the no-drop capacity regime."""
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models import layers as L
from repro.dist.sharding import Sharder, NULL

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
sharder = Sharder(mesh)
key = jax.random.PRNGKey(0)
T, D, E, K, F = 64, 16, 8, 2, 24
p = L.moe_init(key, D, F, E, 0, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (T, D))

ref, aux_ref = L.moe_apply(p, x, top_k=K, capacity_factor=16.0, shd=NULL)

with mesh:
    f = jax.jit(lambda pp, xx: L.moe_apply(pp, xx, top_k=K,
                                           capacity_factor=16.0,
                                           shd=sharder),
                in_shardings=(None, NamedSharding(mesh, P("data", None))))
    got, aux = f(p, x)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5,
                           rtol=2e-5)
np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-5)
# gradients agree too (the a2a constraints must be transparent to AD)
g_ref = jax.grad(lambda pp: jnp.sum(L.moe_apply(pp, x, top_k=K,
                 capacity_factor=16.0, shd=NULL)[0] ** 2))(p)
with mesh:
    g_sh = jax.jit(jax.grad(lambda pp: jnp.sum(L.moe_apply(pp, x, top_k=K,
                   capacity_factor=16.0, shd=sharder)[0] ** 2)))(p)
for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_sh)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                               rtol=5e-5)
print("GROUPED_MOE_EP_OK")
""")
    assert "GROUPED_MOE_EP_OK" in out


@pytest.mark.slow
@pytest.mark.parametrize("shape,axes", [((4,), ("data",)),
                                        ((2, 4), ("data", "model"))])
def test_search_sharded_matches_one_device(shape, axes):
    """Retriever.search_sharded over a state split across the mesh (each
    device scans its own pages, candidates merge over every corpus axis,
    the rerank runs where each row lives) returns what search returns on
    one device."""
    n_dev = 1
    for a in shape:
        n_dev *= a
    out = run_subprocess(f"""
import jax, numpy as np
from repro.data import synthetic
from repro.launch.mesh import make_mesh
from repro.retrieval import Corpus, HPCConfig, Query, Retriever

mesh = make_mesh({shape!r}, {axes!r})
spec = synthetic.CorpusSpec(n_docs=128, n_queries=8, n_patches=8,
                            n_q_patches=4, dim=16, n_topics=4)
data = synthetic.make_retrieval_corpus(jax.random.PRNGKey(1), spec)
corpus = Corpus(data.doc_patches, data.doc_mask, data.doc_salience)
q = Query(data.query_patches, data.query_mask, data.query_salience)
for backend, rerank in [("flat", 12), ("flat", 0), ("float_flat", 0),
                        ("hamming", 12)]:
    r = Retriever(HPCConfig(k=32, backend=backend, kmeans_iters=5,
                            kmeans_restarts=1, rerank=rerank))
    st = r.build(jax.random.PRNGKey(2), corpus)
    s1, i1 = r.search(st, q, k=5)
    sh = r.shard(st, mesh)
    held = {{s.device for s in sh.rerank_codes.addressable_shards}}
    assert len(held) == {n_dev}, held
    s4, i4 = jax.jit(lambda s, qq: r.search_sharded(s, qq, k=5,
                                                    mesh=mesh))(sh, q)
    np.testing.assert_allclose(np.asarray(s4), np.asarray(s1), atol=1e-5,
                               rtol=1e-6)
    # equal scores may come back in another order; every id's score holds
    for a, b, s in zip(np.asarray(i4), np.asarray(i1), np.asarray(s1)):
        for j in np.flatnonzero(a != b):
            assert np.sum(s == s[j]) >= 2, (backend, a, b, s)
print("SEARCH_SHARDED_OK")
""", n_devices=n_dev)
    assert "SEARCH_SHARDED_OK" in out
