"""Cascade backend + composable search-stage API tests.

Covers the PR 7 contract: `search_candidates` restricted scoring on the
stage-capable backends (flat / float_flat / hamming) against restricted
brute-force oracles, the staged funnel's equivalence to `float_flat`
at full budgets, the -1 sentinel at stage boundaries (k > P, p2 > p1),
budget monotonicity, nested-state persistence (tuple aux, no pickle),
1-device-mesh sharding, per-query scan layouts across block sizes, and
the ivf/hnsw declines.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import index as index_mod
from repro.core import late_interaction as li
from repro.core import scan as scan_mod
from repro.data import synthetic
from repro.retrieval import (CascadeConfig, Corpus, HPCConfig, Query,
                             Retriever, get_backend)
from repro.retrieval.cascade import STAGES, CascadeState


@pytest.fixture(scope="module")
def data():
    key = jax.random.PRNGKey(0)
    spec = synthetic.CorpusSpec(n_docs=96, n_queries=12, n_patches=10,
                                n_q_patches=4, dim=24, n_topics=6,
                                dup_per_doc=2)
    return synthetic.make_retrieval_corpus(key, spec)


@pytest.fixture(scope="module")
def nodup_data():
    """Duplicate-free corpus: no exact float-score ties, so top-k id
    comparisons are order-stable across candidate permutations."""
    key = jax.random.PRNGKey(3)
    spec = synthetic.CorpusSpec(n_docs=96, n_queries=12, n_patches=10,
                                n_q_patches=4, dim=24, n_topics=6,
                                dup_per_doc=0)
    return synthetic.make_retrieval_corpus(key, spec)


def _corpus(d):
    return Corpus(d.doc_patches, d.doc_mask, d.doc_salience)


def _queries(d):
    return Query(d.query_patches, d.query_mask, d.query_salience)


def _cfg(backend, **kw):
    kw.setdefault("k", 32)
    kw.setdefault("kmeans_iters", 6)
    return HPCConfig(p=60.0, backend=backend, prune_side="doc", **kw)


def _random_pools(key, n_docs, b, p, frac_invalid=0.2):
    """(B, P) candidate pools: distinct positions + some -1 slots."""
    keys = jax.random.split(key, b)
    rows = [jax.random.permutation(kq, n_docs)[:p] for kq in keys]
    ids = jnp.stack(rows).astype(jnp.int32)
    drop = jax.random.uniform(key, (b, p)) < frac_invalid
    return jnp.where(drop, -1, ids)


# ---------------------------------------------------------------------------
# search_candidates vs restricted brute-force oracles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["flat", "hamming", "float_flat"])
def test_search_candidates_matches_restricted_oracle(data, backend):
    """Restricted search == full search with non-candidates masked out."""
    r = Retriever(_cfg(backend))
    state = r.build(jax.random.PRNGKey(1), _corpus(data))
    b_end = get_backend(backend)
    q = _queries(data)
    n = data.doc_patches.shape[0]
    b = q.embeddings.shape[0]
    k = 8

    pools = _random_pools(jax.random.PRNGKey(2), n, b, 40)
    s_r, i_r = b_end.search_candidates(state, q, pools, k=k)
    s_r, i_r = np.asarray(s_r), np.asarray(i_r)

    # oracle: score every doc via full search, then restrict per pool.
    # Compare scores (tie-robust: duplicate docs / int hamming scores
    # can tie, making exact id order ambiguous) and id->score
    # consistency rather than raw id sequences.
    s_full, i_full = b_end.search(state, q, k=n)
    s_full, i_full = np.asarray(s_full), np.asarray(i_full)
    for qi in range(b):
        score = {int(i): float(s) for i, s in
                 zip(i_full[qi], s_full[qi])}
        pool = set(int(x) for x in np.asarray(pools[qi]) if x >= 0)
        want = sorted((score[p] for p in pool), reverse=True)[:k]
        got_valid = i_r[qi] >= 0
        assert int(got_valid.sum()) == min(k, len(pool))
        assert not got_valid[int(got_valid.sum()):].any()
        for rid, rs in zip(i_r[qi][got_valid], s_r[qi][got_valid]):
            assert int(rid) in pool
            np.testing.assert_allclose(float(rs), score[int(rid)],
                                       rtol=1e-5)
        np.testing.assert_allclose(s_r[qi][got_valid], want, rtol=1e-5)


@pytest.mark.parametrize("backend", ["flat", "hamming", "float_flat"])
def test_search_candidates_full_pool_equals_search(nodup_data, backend):
    """candidate_ids = the whole corpus -> identical to plain search."""
    r = Retriever(_cfg(backend))
    state = r.build(jax.random.PRNGKey(1), _corpus(nodup_data))
    b_end = get_backend(backend)
    q = _queries(nodup_data)
    n = nodup_data.doc_patches.shape[0]
    b = q.embeddings.shape[0]
    all_ids = jnp.tile(jnp.arange(n, dtype=jnp.int32)[None], (b, 1))

    s0, i0 = b_end.search(state, q, k=7)
    s1, i1 = b_end.search_candidates(state, q, all_ids, k=7)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), rtol=1e-6)


def test_search_candidates_none_falls_back_to_search(data):
    for name in ("flat", "hamming", "float_flat", "ivf", "hnsw"):
        cfg = _cfg(name)
        r = Retriever(cfg)
        state = r.build(jax.random.PRNGKey(1), _corpus(data))
        b_end = get_backend(name)
        s0, i0 = b_end.search(state, _queries(data), k=5)
        s1, i1 = b_end.search_candidates(state, _queries(data), None, k=5)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


def test_routing_backends_decline_candidates(data):
    for name in ("ivf", "hnsw"):
        r = Retriever(_cfg(name))
        state = r.build(jax.random.PRNGKey(1), _corpus(data))
        pools = jnp.zeros((12, 4), jnp.int32)
        with pytest.raises(NotImplementedError, match=name):
            get_backend(name).search_candidates(state, _queries(data),
                                                pools, k=3)


def test_base_class_default_declines_candidates():
    from repro.retrieval.base import IndexBackend
    be = IndexBackend()
    with pytest.raises(NotImplementedError, match="search_candidates"):
        be.search_candidates(None, None, jnp.zeros((1, 1), jnp.int32), k=1)


# ---------------------------------------------------------------------------
# The cascade funnel
# ---------------------------------------------------------------------------

def test_cascade_full_budgets_match_float_flat(nodup_data):
    """p1 = p2 = N degenerates the funnel to the exact float scan."""
    n = nodup_data.doc_patches.shape[0]
    r_c = Retriever(_cfg("cascade", cascade=CascadeConfig(p1=n, p2=n)))
    r_f = Retriever(_cfg("float_flat"))
    key = jax.random.PRNGKey(4)
    st_c = r_c.build(key, _corpus(nodup_data))
    st_f = r_f.build(key, _corpus(nodup_data))
    s_c, i_c = r_c.search(st_c, _queries(nodup_data), k=10)
    s_f, i_f = r_f.search(st_f, _queries(nodup_data), k=10)
    np.testing.assert_array_equal(np.asarray(i_c), np.asarray(i_f))
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_f), rtol=1e-5)


def test_cascade_recall_against_flat_oracle(data):
    """At a 33%/12% funnel the cascade must track the exhaustive ADC
    scan (same codebook) on ground-truth recall — the smoke-gate
    criterion at test scale. p2 must exceed the recall depth (k=10),
    else the final stage caps recall structurally."""
    from benchmarks.common import retrieval_metrics

    key = jax.random.PRNGKey(5)
    r_flat = Retriever(_cfg("flat"))
    st_flat = r_flat.build(key, _corpus(data))
    _, i_flat = r_flat.search(st_flat, _queries(data), k=10)
    m_flat = retrieval_metrics(np.asarray(i_flat),
                               np.asarray(data.relevance), 10)

    r_c = Retriever(_cfg("cascade", cascade=CascadeConfig(p1=32, p2=12)))
    st_c = r_c.build(key, _corpus(data))
    _, i_c = r_c.search(st_c, _queries(data), k=10)
    m_c = retrieval_metrics(np.asarray(i_c), np.asarray(data.relevance), 10)
    assert m_c["recall@10"] >= 0.95 * m_flat["recall@10"]


def test_cascade_sentinel_padding_at_stage_boundaries(data):
    """k > p2 > p1: every stage hands -1 rows downstream untouched and
    the final tail is sentinel-padded, not fabricated."""
    r = Retriever(_cfg("cascade", cascade=CascadeConfig(p1=4, p2=16)))
    state = r.build(jax.random.PRNGKey(6), _corpus(data))
    k = 24                                    # k > p2 > p1
    scores, ids = r.search(state, _queries(data), k=k)
    scores, ids = np.asarray(scores), np.asarray(ids)
    assert ids.shape == (12, k)
    # only p1=4 candidates can survive stage 1 -> exactly 4 valid rows
    for qi in range(ids.shape[0]):
        valid = ids[qi] >= 0
        assert valid.sum() == 4
        assert not valid[4:].any()            # valid rows sort first
        # contract: NEG_INF-or-below (stages may emit -1e30 or -inf)
        assert np.all(scores[qi][~valid] <= -1e30)
        assert len(set(ids[qi][valid])) == valid.sum()   # no duplicates


def test_cascade_k_exceeds_corpus(data):
    n = data.doc_patches.shape[0]
    r = Retriever(_cfg("cascade", cascade=CascadeConfig(p1=n, p2=n)))
    state = r.build(jax.random.PRNGKey(6), _corpus(data))
    scores, ids = r.search(state, _queries(data), k=n + 8)
    ids = np.asarray(ids)
    assert ids.shape[1] == n + 8
    assert np.all(ids[:, n:] == -1)


def _oracle_recall(ids, oracle_ids):
    """Mean |returned ∩ oracle top-k| / k per query."""
    ids, oracle_ids = np.asarray(ids), np.asarray(oracle_ids)
    hits = [len(set(r[r >= 0]) & set(o)) / oracle_ids.shape[1]
            for r, o in zip(ids, oracle_ids)]
    return float(np.mean(hits))


def test_cascade_budget_monotonicity(nodup_data):
    """Wider budgets never lower recall against the exact-float oracle.

    The guarantee is set-theoretic: the pool reaching the float rerank
    is nested as a budget widens (hamming top-p1 at p2 >= p1; ADC
    top-p2 of a fixed hamming pool as p2 grows), and any float-oracle
    top-k member inside a pool always survives the float rerank. It
    holds only against the FLOAT oracle — ground-truth recall can
    legitimately dip when a wider p1 lets the noisier ADC middle stage
    displace candidates (quantization noise), which is why the smoke
    gate measures both sides against ground truth instead of assuming
    monotonicity there.
    """
    n = nodup_data.doc_patches.shape[0]
    key = jax.random.PRNGKey(7)
    r_oracle = Retriever(_cfg("float_flat"))
    st_o = r_oracle.build(key, _corpus(nodup_data))
    _, oracle_ids = r_oracle.search(st_o, _queries(nodup_data), k=10)

    # p1 ladder with the ADC stage wide open (p2 = n): pool = hamming
    # top-p1, nested in p1.
    r_p1 = []
    for p1 in (8, 24, 48, n):
        r = Retriever(_cfg("cascade", cascade=CascadeConfig(p1=p1, p2=n)))
        st = r.build(key, _corpus(nodup_data))
        _, ids = r.search(st, _queries(nodup_data), k=10)
        r_p1.append(_oracle_recall(ids, oracle_ids))
    assert all(b >= a for a, b in zip(r_p1, r_p1[1:])), r_p1
    assert r_p1[-1] == 1.0                      # full budget = exact scan

    # p2 ladder at fixed p1: pool = ADC top-p2 of one fixed hamming
    # pool, nested in p2.
    r_p2 = []
    for p2 in (4, 12, 24, 48):
        r = Retriever(_cfg("cascade", cascade=CascadeConfig(p1=48, p2=p2)))
        st = r.build(key, _corpus(nodup_data))
        _, ids = r.search(st, _queries(nodup_data), k=10)
        r_p2.append(_oracle_recall(ids, oracle_ids))
    assert all(b >= a for a, b in zip(r_p2, r_p2[1:])), r_p2


# ---------------------------------------------------------------------------
# Persistence, sharding, accounting
# ---------------------------------------------------------------------------

def test_cascade_save_load_roundtrip(data, tmp_path):
    cfg = _cfg("cascade", cascade=CascadeConfig(p1=24, p2=8))
    r = Retriever(cfg)
    state = r.build(jax.random.PRNGKey(8), _corpus(data))
    path = r.save(str(tmp_path / "casc_idx"), state)

    restored = r.load(path)
    assert isinstance(restored.backend_state, CascadeState)
    assert restored.backend_state.p1 == 24
    assert restored.backend_state.p2 == 8
    assert restored.backend_state.members[0].bits == cfg.bits
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    s0, i0 = r.search(state, _queries(data), k=5)
    s1, i1 = r.search(restored, _queries(data), k=5)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), rtol=1e-6)


def test_cascade_load_rejects_other_backend_file(data, tmp_path):
    r_flat = Retriever(_cfg("flat"))
    state = r_flat.build(jax.random.PRNGKey(8), _corpus(data))
    path = r_flat.save(str(tmp_path / "flat_idx"), state)
    with pytest.raises(ValueError, match="flat"):
        get_backend("cascade").load(path)


def test_cascade_shard_places_state_and_preserves_results(data):
    cfg = _cfg("cascade", cascade=CascadeConfig(p1=24, p2=8))
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    r = Retriever(cfg)
    state = r.build(jax.random.PRNGKey(9), _corpus(data))
    s0, i0 = r.search(state, _queries(data), k=5)

    sharded = r.shard(state, mesh)
    for leaf in jax.tree.leaves(sharded):
        assert leaf.sharding.mesh.shape == mesh.shape
    s1, i1 = r.search(sharded, _queries(data), k=5)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), atol=1e-5)


def test_cascade_build_on_1dev_mesh_matches_single_host(data):
    cfg = _cfg("cascade", cascade=CascadeConfig(p1=24, p2=8))
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    r = Retriever(cfg)
    st_mesh = r.build(jax.random.PRNGKey(9), _corpus(data), mesh=mesh)
    st_local = r.build(jax.random.PRNGKey(9), _corpus(data))
    s_m, i_m = r.search(st_mesh, _queries(data), k=5)
    s_l, i_l = r.search(st_local, _queries(data), k=5)
    np.testing.assert_array_equal(np.asarray(i_m), np.asarray(i_l))
    np.testing.assert_allclose(np.asarray(s_m), np.asarray(s_l), atol=1e-5)


def test_cascade_storage_and_stats_compose(data):
    r = Retriever(_cfg("cascade", cascade=CascadeConfig(p1=24, p2=8)))
    state = r.build(jax.random.PRNGKey(10), _corpus(data))
    sb = r.storage_bytes(state)
    assert set(f"stage_{s}" for s in STAGES) <= set(sb)
    assert sb["payload"] == sum(sb[f"stage_{s}"] for s in STAGES)
    stats = r.build_stats(state)
    assert stats["p1"] == 24.0 and stats["p2"] == 8.0


def test_cascade_manifest_registered():
    from repro.analysis.manifests import get_manifest
    m = get_manifest("search_cascade")
    fn, args = m.trace(1 << 12)
    scores, ids = jax.eval_shape(fn, *args)
    assert scores.dtype == jnp.float32 and ids.dtype == jnp.int32


# ---------------------------------------------------------------------------
# Per-query scan layouts (the engine primitives under the stage API)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block_docs", [3, 7, 64])
def test_maxsim_topk_per_query_matches_oracle(block_docs):
    key = jax.random.PRNGKey(11)
    kq, kd, km = jax.random.split(key, 3)
    b, p, mq, md, d, k = 3, 17, 4, 6, 8, 5
    q = jax.random.normal(kq, (b, mq, d))
    qm = jnp.ones((b, mq), bool)
    docs = jax.random.normal(kd, (b, p, md, d))
    dm = jax.random.uniform(km, (b, p, md)) > 0.2
    ids = jnp.tile(jnp.arange(p, dtype=jnp.int32)[None], (b, 1))
    valid = jnp.ones((b, p), bool)

    s, i = scan_mod.maxsim_topk(
        q, qm, docs, dm, k=k, doc_ids=ids, valid=valid,
        scan=scan_mod.ScanConfig(block_docs=block_docs, impl="jnp"))
    # oracle: unblocked per-query float maxsim
    want = jnp.stack([li.maxsim(q[j:j + 1], qm[j:j + 1], docs[j],
                                dm[j])[0] for j in range(b)])
    want_s, want_i = jax.lax.top_k(want, k)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(want_i))


@pytest.mark.parametrize("block_docs", [3, 7, 64])
def test_hamming_topk_per_query_matches_oracle(block_docs):
    key = jax.random.PRNGKey(12)
    kq, kd, km = jax.random.split(key, 3)
    b, p, mq, md, bits, k = 3, 17, 4, 6, 5, 5
    q_codes = jax.random.randint(kq, (b, mq), 0, 1 << bits, jnp.uint16)
    qm = jnp.ones((b, mq), bool)
    d_codes = jax.random.randint(kd, (b, p, md), 0, 1 << bits, jnp.uint16)
    dm = jax.random.uniform(km, (b, p, md)) > 0.2

    s, i = scan_mod.hamming_maxsim_topk(
        q_codes, qm, d_codes, dm, bits=bits, k=k,
        scan=scan_mod.ScanConfig(block_docs=block_docs, impl="jnp"))
    want = jnp.stack([li.binary_maxsim(q_codes[j:j + 1], qm[j:j + 1],
                                       d_codes[j], dm[j], bits)[0]
                      for j in range(b)])
    want_s, want_i = jax.lax.top_k(want, k)
    assert s.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(s), np.asarray(want_s))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(want_i))


@pytest.mark.parametrize("impl", ["jnp", "interpret"])
def test_per_query_layouts_impl_parity(impl):
    """The Pallas (interpreter) block scorer agrees with the jnp path on
    the new per-query float/hamming layouts."""
    key = jax.random.PRNGKey(13)
    kq, kd = jax.random.split(key)
    b, p, mq, md, d, k = 2, 9, 3, 4, 8, 4
    q = jax.random.normal(kq, (b, mq, d))
    qm = jnp.ones((b, mq), bool)
    docs = jax.random.normal(kd, (b, p, md, d))
    dm = jnp.ones((b, p, md), bool)
    s, i = scan_mod.maxsim_topk(
        q, qm, docs, dm, k=k,
        scan=scan_mod.ScanConfig(block_docs=4, impl=impl))
    s_ref, i_ref = scan_mod.maxsim_topk(
        q, qm, docs, dm, k=k,
        scan=scan_mod.ScanConfig(block_docs=4, impl="jnp"))
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))

    q_codes = jax.random.randint(kq, (b, mq), 0, 32, jnp.uint16)
    d_codes = jax.random.randint(kd, (b, p, md), 0, 32, jnp.uint16)
    s, i = scan_mod.hamming_maxsim_topk(
        q_codes, qm, d_codes, dm, bits=5, k=k,
        scan=scan_mod.ScanConfig(block_docs=4, impl=impl))
    s_ref, i_ref = scan_mod.hamming_maxsim_topk(
        q_codes, qm, d_codes, dm, bits=5, k=k,
        scan=scan_mod.ScanConfig(block_docs=4, impl="jnp"))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s_ref))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))


def test_gather_candidates_sentinel_contract():
    """-1 pool slots gather row 0 safely but stay invalid/-1 in output."""
    ids = jnp.array([[2, -1, 0], [-1, -1, 1]], jnp.int32)
    doc_ids = jnp.array([10, 11, 12], jnp.int32)
    payload = jnp.arange(3 * 2).reshape(3, 2)
    out_ids, valid, (g,) = index_mod._gather_candidates(ids, doc_ids,
                                                        payload)
    np.testing.assert_array_equal(np.asarray(out_ids),
                                  [[12, -1, 10], [-1, -1, 11]])
    np.testing.assert_array_equal(np.asarray(valid),
                                  [[True, False, True],
                                   [False, False, True]])
    assert g.shape == (2, 3, 2)
