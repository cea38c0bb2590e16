"""`Retriever.build_sharded`: the streaming build of a corpus split over a
mesh, checked on four virtual CPU devices against the one-device build,
`add` per chunk and `compact` of the same chunks."""
from __future__ import annotations

import pytest

from tests.conftest import run_subprocess

_SETUP = """
import jax, jax.numpy as jnp, numpy as np
from repro.data import synthetic
from repro.launch.mesh import make_mesh
from repro.retrieval import Corpus, HPCConfig, Query, Retriever

N, CHUNK = 512, 64
mesh = make_mesh((4,), ("data",))
spec = synthetic.CorpusSpec(n_docs=N, n_queries=8, n_patches=8,
                            n_q_patches=4, dim=16, n_topics=4)
data = synthetic.make_retrieval_corpus(jax.random.PRNGKey(1), spec)
corpus = Corpus(data.doc_patches, data.doc_mask, data.doc_salience)
query = Query(data.query_patches, data.query_mask, data.query_salience)
key = jax.random.PRNGKey(2)


def chunk(c):
    return c * CHUNK, Corpus(*(x[c * CHUNK:(c + 1) * CHUNK]
                               for x in corpus))


# dealt to the devices in turn (two chunks a device), chunk 0 first
ORDER = [0, 2, 4, 6, 1, 3, 5, 7]
"""


@pytest.mark.parametrize("backend,rerank", [("flat", 12), ("hamming", 12),
                                            ("float_flat", 0)])
def test_sharded_build_equals_build_add_compact(backend, rerank):
    """Fed its chunks dealt over four devices, the build gives the
    codebook and, row for row, the codes, masks, ids and rerank rows that
    `build` on the first chunk, `add` per chunk and `compact` give; each
    device holds exactly its quarter of the rows; and `search_sharded`
    over it answers as `search` over the one-device state."""
    out = run_subprocess(_SETUP + f"""
r = Retriever(HPCConfig(k=32, backend={backend!r}, kmeans_iters=5,
                        kmeans_restarts=1, rerank={rerank}))
one = r.build(key, chunk(0)[1])
for c in range(1, N // CHUNK):
    one = r.add(one, chunk(c)[1])
one = r.compact(one)
seg = r.backend._segmented(one)
assert seg.n_segments == 1
sharded = r.build_sharded(key, (chunk(c) for c in ORDER), n_docs=N,
                          mesh=mesh)

# the streaming build's state is monolithic, laid out as `shard` lays it
assert r.backend._segmented(sharded) is None
def specs(st):
    return [x.sharding.spec for x in jax.tree.leaves(st)]
assert specs(sharded) == specs(r.shard(sharded, mesh))
bs = sharded.backend_state
payload = bs.index if r.backend._is_wrapper(bs) else bs
pairs = {{"codebook": (sharded.codebook, one.codebook),
          "rerank_codes": (sharded.rerank_codes, one.rerank_codes[:N]),
          "rerank_mask": (sharded.rerank_mask, one.rerank_mask[:N])}}
for name in payload._fields:
    a, b = getattr(payload, name), getattr(seg.segments[0], name)
    pairs[name] = (a, b[:N] if jnp.ndim(b) and name != "codebook" else b)
for name, (a, b) in pairs.items():
    assert a.dtype == b.dtype and a.shape == b.shape, (name, a, b)
    assert np.array_equal(np.asarray(a), np.asarray(b)), name

# each device holds rows [d N/4, (d+1) N/4) of every row leaf
for x in jax.tree.leaves(sharded):
    if x.ndim == 0 or x.sharding.is_fully_replicated:
        continue
    assert len(x.addressable_shards) == 4
    for s in x.addressable_shards:
        d = mesh.devices.tolist().index(s.device)
        assert (s.index[0].start, s.index[0].stop) == (d * N // 4,
                                                       (d + 1) * N // 4)
        assert np.array_equal(np.asarray(s.data),
                              np.asarray(x)[d * N // 4:(d + 1) * N // 4])

s1, i1 = r.search(one, query, k=10)
search4 = jax.jit(lambda st, q: r.search_sharded(st, q, k=10, mesh=mesh))
s4, i4 = search4(sharded, query)
# the merge's all-gathers carry their scope into the compiled program
hlo = search4.lower(sharded, query).compile().as_text()
assert any("all-gather" in line and "search.shard_merge" in line
           for line in hlo.splitlines())
np.testing.assert_allclose(np.asarray(s4), np.asarray(s1), atol=1e-5,
                           rtol=1e-6)
# equal scores may come back in another order; every id's score holds
for a, b, s in zip(np.asarray(i4), np.asarray(i1), np.asarray(s1)):
    for j in np.flatnonzero(a != b):
        assert np.sum(s == s[j]) >= 2, (a, b, s)
print("SHARDED_BUILD_OK")
""", n_devices=4)
    assert "SHARDED_BUILD_OK" in out


def test_sharded_build_refuses_what_it_cannot_build():
    """A backend that routes queries through its own structure, a first
    chunk that is not at offset 0, a chunk across two devices' shares and
    chunks that leave docs out are refused."""
    out = run_subprocess(_SETUP + """
import pytest

r = Retriever(HPCConfig(k=32, backend="flat", kmeans_iters=5,
                        kmeans_restarts=1, rerank=12))
with pytest.raises(NotImplementedError, match="exhaustive"):
    Retriever(HPCConfig(k=32, backend="ivf")).build_sharded(
        key, (chunk(c) for c in ORDER), n_docs=N, mesh=mesh)
with pytest.raises(ValueError, match="offset 64"):
    r.build_sharded(key, (chunk(c) for c in [1, 0]), n_docs=N, mesh=mesh)
across = (96, Corpus(*(x[96:160] for x in corpus)))
with pytest.raises(ValueError, match="one device's share"):
    r.build_sharded(key, iter([chunk(0), across]), n_docs=N, mesh=mesh)
with pytest.raises(ValueError, match="cover 448 of 512"):
    r.build_sharded(key, (chunk(c) for c in ORDER[:-1]), n_docs=N,
                    mesh=mesh)
print("REFUSED_OK")
""", n_devices=4)
    assert "REFUSED_OK" in out
