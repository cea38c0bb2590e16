"""HNSW graph backend tests (core/graph.py, retrieval/hnsw.py).

Covers: build determinism under a fixed key, graph structural invariants
(degree caps, left-packed rows, connectivity), save/load round-trip
parity, sharding, recall vs `ivf` at an equal scanned-candidate budget,
`ef_search` monotonicity, and the -1 sentinel contract.
"""
from collections import deque

import jax
import numpy as np
import pytest

from benchmarks.ann_compare import tie_aware_recall_at_k
from repro.core import graph as graph_mod
from repro.core import late_interaction as li
from repro.core.graph import HNSWConfig
from repro.core.index import IVFConfig
from repro.data import synthetic
from repro.retrieval import Corpus, HPCConfig, Query, Retriever

K = 10
HNSW_CFG = HNSWConfig(m=8, ef_construction=48, ef_search=64, levels=4)
BASE = dict(k=64, p=60.0, prune_side="doc", kmeans_iters=10)


@pytest.fixture(scope="module")
def data():
    spec = synthetic.CorpusSpec(n_docs=256, n_queries=32, n_patches=16,
                                n_q_patches=4, dim=32, n_topics=8,
                                dup_per_doc=3)
    return synthetic.make_retrieval_corpus(jax.random.PRNGKey(0), spec)


def _corpus(data):
    return Corpus(data.doc_patches, data.doc_mask, data.doc_salience)


def _queries(data):
    return Query(data.query_patches, data.query_mask, data.query_salience)


@pytest.fixture(scope="module")
def hnsw_state(data):
    r = Retriever(HPCConfig(backend="hnsw", hnsw=HNSW_CFG, **BASE))
    return r, r.build(jax.random.PRNGKey(1), _corpus(data))


@pytest.fixture(scope="module")
def flat_oracle(data):
    """Exhaustive fused scan over the same codebook (same build key)."""
    r = Retriever(HPCConfig(backend="flat", **BASE))
    state = r.build(jax.random.PRNGKey(1), _corpus(data))
    scores, ids = r.search(state, _queries(data), k=K)
    return np.asarray(scores), np.asarray(ids)


# ---------------------------------------------------------------------------
# Build: determinism + structural invariants
# ---------------------------------------------------------------------------

def test_graph_build_deterministic(hnsw_state):
    _, state = hnsw_state
    ix = state.backend_state.index
    key = jax.random.PRNGKey(42)
    g1 = graph_mod.build_hnsw(key, ix.codes, ix.mask, ix.codebook, HNSW_CFG)
    g2 = graph_mod.build_hnsw(key, ix.codes, ix.mask, ix.codebook, HNSW_CFG)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_graph_invariants(hnsw_state):
    _, state = hnsw_state
    ix = state.backend_state.index
    nbrs = np.asarray(ix.neighbors)
    n = ix.doc_vecs.shape[0]
    assert nbrs.shape == (HNSW_CFG.levels, n, 2 * HNSW_CFG.m)
    assert nbrs.min() >= -1 and nbrs.max() < n
    for lev in range(HNSW_CFG.levels):
        rows = nbrs[lev]
        # no self-loops
        assert not np.any(rows == np.arange(n)[:, None])
        # rows are left-packed: no valid id to the right of a -1 slot
        filled = rows >= 0
        assert np.all(filled[:, :-1] | ~filled[:, 1:])
        # upper levels respect the m (not 2m) degree cap
        if lev >= 1:
            assert filled.sum(axis=1).max() <= HNSW_CFG.m
    # level-0 graph is one undirected component (reachable everywhere)
    adj = [set() for _ in range(n)]
    for i in range(n):
        for v in nbrs[0, i]:
            if v >= 0:
                adj[i].add(int(v))
                adj[int(v)].add(i)
    seen = {0}
    dq = deque([0])
    while dq:
        u = dq.popleft()
        for v in adj[u]:
            if v not in seen:
                seen.add(v)
                dq.append(v)
    assert len(seen) == n


def test_build_stats(hnsw_state):
    r, state = hnsw_state
    stats = r.build_stats(state)
    assert 0 < stats["mean_degree_l0"] <= 2 * HNSW_CFG.m
    assert stats["levels"] == HNSW_CFG.levels
    assert stats["entry_level"] == int(
        np.asarray(state.backend_state.index.node_level).max())


# ---------------------------------------------------------------------------
# save / load + sharding
# ---------------------------------------------------------------------------

def test_save_load_roundtrip(data, hnsw_state, tmp_path):
    r, state = hnsw_state
    path = r.save(str(tmp_path / "hnsw_idx"), state)
    restored = r.load(path)
    assert restored.backend_state.ef_search == HNSW_CFG.ef_search
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    s0, i0 = r.search(state, _queries(data), k=K)
    s1, i1 = r.search(restored, _queries(data), k=K)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


def test_shard_places_state_and_preserves_results(data, hnsw_state):
    r, state = hnsw_state
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    s0, i0 = r.search(state, _queries(data), k=K)
    sharded = r.shard(state, mesh)
    for leaf in jax.tree.leaves(sharded):
        assert leaf.sharding.mesh.shape == mesh.shape
    s1, i1 = r.search(sharded, _queries(data), k=K)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), atol=1e-5)


# ---------------------------------------------------------------------------
# Recall vs ivf at an equal scanned-candidate budget
# ---------------------------------------------------------------------------

def test_recall_meets_ivf_at_equal_budget(data, hnsw_state, flat_oracle):
    """Acceptance: at the same number of candidates through the fused
    scan (ef_search == n_probe * bucket_cap < n_docs), the graph router
    must meet or beat the centroid router's recall@10 against the
    exhaustive scan over the same codebook (tie-aware: near-duplicate
    docs quantize to identical codes, so equal-scored substitutes count).
    """
    r_h, st_h = hnsw_state
    oracle_scores, _ = flat_oracle
    r_i = Retriever(HPCConfig(
        backend="ivf", ivf=IVFConfig(n_list=16, n_probe=2, iters=8), **BASE))
    st_i = r_i.build(jax.random.PRNGKey(1), _corpus(data))
    cap = st_i.backend_state.index.bucket_codes.shape[1]
    budget = 2 * cap
    assert budget == HNSW_CFG.ef_search        # equal scanned budgets
    assert budget < data.doc_patches.shape[0]  # strictly less than flat

    s_h, i_h = r_h.search(st_h, _queries(data), k=K)
    s_i, i_i = r_i.search(st_i, _queries(data), k=K)
    rec_h = tie_aware_recall_at_k(np.asarray(s_h), np.asarray(i_h),
                                  oracle_scores, K)
    rec_i = tie_aware_recall_at_k(np.asarray(s_i), np.asarray(i_i),
                                  oracle_scores, K)
    assert rec_h >= rec_i, (rec_h, rec_i)
    assert rec_h >= 0.9, rec_h

    # Against the *float* (ColPali-Full) oracle both routers sit at the
    # quantization ceiling and differ only through which member of a
    # quantization-tied group they surface — require hnsw within noise.
    fs = np.asarray(li.maxsim(data.query_patches, data.query_mask,
                              data.doc_patches, data.doc_mask))
    thresh = np.sort(fs, axis=1)[:, ::-1][:, K - 1]

    def float_recall(ids):
        out = []
        for qi in range(ids.shape[0]):
            v = np.asarray(ids[qi][:K])
            v = v[v >= 0]
            tol = 1e-5 * max(abs(float(thresh[qi])), 1.0)
            out.append(np.sum(fs[qi, v] >= thresh[qi] - tol) / K)
        return float(np.mean(out))

    assert float_recall(np.asarray(i_h)) >= float_recall(np.asarray(i_i)) - 0.05


def test_ef_search_monotonicity(data, hnsw_state, flat_oracle):
    """Recall is non-decreasing as the beam widens (same built graph)."""
    _, state = hnsw_state
    ix = state.backend_state.index
    oracle_scores, _ = flat_oracle
    q = _queries(data)
    prev = -1.0
    for ef in (10, 16, 32, 64, 128):
        s, ids = graph_mod.search_hnsw(ix, q.embeddings, q.mask,
                                       ef_search=ef, k=K)
        rec = tie_aware_recall_at_k(np.asarray(s), np.asarray(ids),
                                    oracle_scores, K)
        assert rec >= prev, (ef, rec, prev)
        prev = rec
    assert prev >= 0.95  # the widest beam is near-exhaustive


# ---------------------------------------------------------------------------
# Sentinel contract
# ---------------------------------------------------------------------------

def test_sentinel_rows_when_beam_exceeds_corpus():
    """k > n_docs: the tail rows must be -1 ids with NEG_INF scores."""
    spec = synthetic.CorpusSpec(n_docs=12, n_queries=4, n_patches=8,
                                n_q_patches=4, dim=16, n_topics=2,
                                dup_per_doc=1)
    data = synthetic.make_retrieval_corpus(jax.random.PRNGKey(2), spec)
    cfg = HPCConfig(k=8, p=100.0, prune_side="none", kmeans_iters=5,
                    backend="hnsw",
                    hnsw=HNSWConfig(m=4, ef_construction=16, ef_search=32,
                                    levels=2))
    r = Retriever(cfg)
    state = r.build(jax.random.PRNGKey(3), _corpus(data))
    scores, ids = r.search(state, _queries(data), k=16)
    ids, scores = np.asarray(ids), np.asarray(scores)
    assert np.all(np.sum(ids >= 0, axis=1) == 12)   # every real doc found
    assert np.all(ids[:, 12:] == -1)                # tail is sentinel
    assert np.all(scores[ids < 0] <= li.NEG_INF / 2)
    # k beyond the ef_search budget pads (matching search_ivf), not fails
    s2, i2 = r.search(state, _queries(data), k=40)
    s2, i2 = np.asarray(s2), np.asarray(i2)
    assert i2.shape == (4, 40)
    assert np.all(np.sum(i2 >= 0, axis=1) == 12)
    assert np.all(s2[i2 < 0] <= li.NEG_INF / 2)
    # metrics accounting must ignore the sentinel rows, not index with -1
    from benchmarks.common import retrieval_metrics
    m = retrieval_metrics(ids, np.asarray(data.relevance), 10)
    assert 0.0 <= m["hit@10"] <= 1.0
