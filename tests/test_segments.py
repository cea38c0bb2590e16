"""Segmented LSM corpus store: mutation API across every backend.

Contract under test (docs/design.md §9):

  * `Retriever.add` / `delete` / `compact` work on all six backends
    without a rebuild; a grown-and-pruned index answers with recall@10
    within 1% of a from-scratch rebuild of the same live corpus.
  * Deleted doc ids never surface; when k exceeds the live-doc count the
    tail is padded with ``-1`` sentinels.
  * Delete-then-add of the same doc_id resolves to the newest segment.
  * Search after `compact` is bit-consistent with search before it.
    For ivf "bit-consistent" means score-bit-consistent: compaction
    re-buckets through the (unchanged) routing centroids, which changes
    scan order, so `lax.top_k`'s position-based tie-breaking may permute
    ids *within an equal-score tie group* — scores stay bit-identical.
    All other backends preserve scan order under compaction
    (`gather_live_rows` keeps slot order) and are bit-exact on both
    scores and ids — except the cascade's float-rerank scores, which
    agree to ULP tolerance.
  * Segmented states round-trip through `save`/`load` (format v2);
    future-versioned files and non-index files fail with clear errors.
  * Accounting: `build_stats` reports live vs tombstoned docs,
    `storage_bytes` reports live-only per-segment payload.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.graph import HNSWConfig
from repro.core.index import IVFConfig
from repro.data import synthetic
from repro.retrieval import Corpus, HPCConfig, Query, Retriever

BACKENDS = ["flat", "float_flat", "hamming", "ivf", "hnsw", "cascade"]

# ivf compaction re-buckets (scan order changes), so equal-score ties may
# permute; every other backend folds segments in scan order and is
# bit-exact on ids too.
BITEXACT_IDS = {"flat", "float_flat", "hamming", "hnsw", "cascade"}
# the cascade's last stage is a float MaxSim over gathered candidate rows,
# whose Mq-sum XLA may reassociate between the segmented and compacted
# programs: its scores agree to ULP tolerance, not bit for bit
ULP_SCORES = {"cascade"}

SPEC = synthetic.CorpusSpec(n_docs=240, n_queries=32, n_patches=8,
                            n_q_patches=4, dim=32, n_topics=6,
                            patches_per_topic=8, noise=0.1)
N_BASE, N_D1, N_TOTAL = 180, 220, 240
DEAD = [3, 10, 181, 200, 224]
UPSERT_ID, UPSERT_SRC = 5, 220   # doc 5 := doc 220's content


def _cfg(backend):
    return HPCConfig(k=64, p=80.0, backend=backend, kmeans_iters=10,
                     kmeans_restarts=2,
                     ivf=IVFConfig(n_list=8, n_probe=6, bucket_cap=64),
                     hnsw=HNSWConfig(m=6, ef_construction=32, ef_search=64,
                                     levels=3),
                     rerank=32)


def _recall_vs(ids, gt, k=10):
    hits, tot = 0, 0
    for a, b in zip(np.asarray(ids)[:, :k], gt):
        hits += len(set(int(x) for x in a if x >= 0) & set(b[:k].tolist()))
        tot += k
    return hits / tot


def _gt_topk(q_emb, q_mask, d_emb, d_mask, ids, k=10):
    out = []
    for b in range(q_emb.shape[0]):
        sims = np.einsum("md,npd->mnp", q_emb[b], d_emb)
        sims = np.where(d_mask[None, :, :], sims, -np.inf)
        score = (sims.max(-1) * q_mask[b][:, None]).sum(0)
        out.append(ids[np.argsort(-score)[:k]])
    return out


@pytest.fixture(scope="module")
def data():
    return synthetic.make_retrieval_corpus(jax.random.PRNGKey(7), SPEC)


def _slice(data, lo, hi):
    return Corpus(jnp.asarray(np.asarray(data.doc_patches)[lo:hi]),
                  jnp.asarray(np.asarray(data.doc_mask)[lo:hi]),
                  jnp.asarray(np.asarray(data.doc_salience)[lo:hi]))


@pytest.fixture(scope="module", params=BACKENDS)
def churned(request, data):
    """One full mutation lifecycle per backend, computed once per module.

    build(0..180) -> add(180..220) -> add(220..240) -> delete(DEAD)
    -> upsert(doc 5 := doc 220) ; plus a from-scratch rebuild of the same
    live corpus and the exact float-MaxSim ground truth over it.
    """
    backend = request.param
    query = Query(data.query_patches, data.query_mask, data.query_salience)
    r = Retriever(_cfg(backend))
    key = jax.random.PRNGKey(0)

    st = r.build(key, _slice(data, 0, N_BASE))
    st = r.add(st, _slice(data, N_BASE, N_D1))       # ids 180..219
    st = r.add(st, _slice(data, N_D1, N_TOTAL))      # ids 220..239
    st = r.delete(st, np.array(DEAD))
    st = r.add(st, _slice(data, UPSERT_SRC, UPSERT_SRC + 1),
               doc_ids=np.array([UPSERT_ID]))
    s_seg, i_seg = r.search(st, query, k=10)

    # live corpus with the upsert applied, for both rebuild and oracle
    emb = np.asarray(data.doc_patches).copy()
    msk = np.asarray(data.doc_mask).copy()
    sal = np.asarray(data.doc_salience).copy()
    emb[UPSERT_ID], msk[UPSERT_ID], sal[UPSERT_ID] = (
        emb[UPSERT_SRC], msk[UPSERT_SRC], sal[UPSERT_SRC])
    live_ids = np.array([i for i in range(N_TOTAL) if i not in DEAD])
    rb_state = r.build(key, Corpus(jnp.asarray(emb[live_ids]),
                                   jnp.asarray(msk[live_ids]),
                                   jnp.asarray(sal[live_ids])))
    _, i_rb = r.search(rb_state, query, k=10)
    i_rb = np.asarray(i_rb)
    i_rb_global = np.where(i_rb >= 0, live_ids[np.maximum(i_rb, 0)], -1)

    gt = _gt_topk(np.asarray(query.embeddings), np.asarray(query.mask),
                  emb[live_ids], msk[live_ids], live_ids)

    st_c = r.compact(st)
    s_c, i_c = r.search(st_c, query, k=10)

    return {"backend": backend, "retriever": r, "query": query,
            "state": st, "state_compact": st_c, "live_ids": live_ids,
            "scores": np.asarray(s_seg), "ids": np.asarray(i_seg),
            "scores_compact": np.asarray(s_c), "ids_compact": np.asarray(i_c),
            "ids_rebuild": i_rb_global, "gt": gt}


# ---------------------------------------------------------------------------
# Recall parity with a from-scratch rebuild (the tentpole acceptance bar)
# ---------------------------------------------------------------------------

def test_churn_recall_within_1pct_of_rebuild(churned):
    rec_seg = _recall_vs(churned["ids"], churned["gt"])
    rec_rb = _recall_vs(churned["ids_rebuild"], churned["gt"])
    assert rec_seg >= rec_rb - 0.01, (churned["backend"], rec_seg, rec_rb)


def test_compact_preserves_recall(churned):
    rec_seg = _recall_vs(churned["ids"], churned["gt"])
    rec_c = _recall_vs(churned["ids_compact"], churned["gt"])
    assert rec_c >= rec_seg - 0.01, (churned["backend"], rec_c, rec_seg)


def test_deleted_ids_never_surface(churned):
    surfaced = set(churned["ids"].ravel().tolist())
    surfaced |= set(churned["ids_compact"].ravel().tolist())
    assert not (surfaced & set(DEAD)), (churned["backend"],
                                        surfaced & set(DEAD))


# ---------------------------------------------------------------------------
# Compact bit-consistency (scores everywhere; ids except ivf tie groups)
# ---------------------------------------------------------------------------

def test_compact_bit_consistency(churned):
    backend = churned["backend"]
    s0, s1 = churned["scores"], churned["scores_compact"]
    i0, i1 = churned["ids"], churned["ids_compact"]
    if backend in ULP_SCORES:
        np.testing.assert_allclose(s1, s0, atol=1e-5, rtol=1e-6,
                                   err_msg=backend)
    else:
        assert np.array_equal(s0, s1), backend
    if backend in BITEXACT_IDS:
        assert np.array_equal(i0, i1), backend
    else:
        # ivf: ids may permute only inside an equal-score tie group —
        # every differing position's score must be tied (duplicated)
        # within its row
        for b, j in np.argwhere(i0 != i1):
            row = s0[b]
            assert np.sum(row == row[j]) >= 2, (backend, b, j, row)


# ---------------------------------------------------------------------------
# Mass deletion: k > live-doc-count pads -1 sentinels
# ---------------------------------------------------------------------------

def test_k_exceeding_live_docs_pads_sentinels(churned, data):
    r = Retriever(_cfg(churned["backend"]))
    st = r.build(jax.random.PRNGKey(0), _slice(data, 0, 5))
    st = r.delete(st, np.arange(3))
    _, ids = r.search(st, churned["query"], k=10)
    ids = np.asarray(ids)
    valid = ids[ids >= 0]
    assert set(valid.tolist()) <= {3, 4}, (churned["backend"], ids)
    assert (ids >= 0).sum(axis=1).max() <= 2, (churned["backend"], ids)


# ---------------------------------------------------------------------------
# Delete-then-add of the same doc_id resolves to the newest segment
# ---------------------------------------------------------------------------

def test_delete_then_add_newest_wins(churned):
    backend = churned["backend"]
    rng = np.random.default_rng(11)
    dim, n, m = 32, 10, 8

    def unit(shape):
        x = rng.standard_normal(shape).astype(np.float32)
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    # unit-norm patches: a doc's own patches are its best match (self
    # dot = 1, cross dots < 1 whp at dim 32)
    emb = unit((n, m, dim))
    new = unit((1, m, dim))
    mask = np.ones((n, m), bool)
    sal = np.ones((n, m), np.float32)

    r = Retriever(_cfg(backend))
    st = r.build(jax.random.PRNGKey(0),
                 Corpus(jnp.asarray(emb), jnp.asarray(mask),
                        jnp.asarray(sal)))
    st = r.delete(st, np.array([2]))
    st = r.add(st, Corpus(jnp.asarray(new), jnp.asarray(mask[:1]),
                          jnp.asarray(sal[:1])),
               doc_ids=np.array([2]))

    def top1(patches):
        q = Query(jnp.asarray(patches[None]), jnp.asarray(mask[:1]),
                  jnp.asarray(sal[:1]))
        _, ids = r.search(st, q, k=3)
        return int(np.asarray(ids)[0, 0])

    # querying with the new content's own patches must hit the re-added
    # doc; the old (tombstoned-then-replaced) content must not
    assert top1(new[0]) == 2, backend
    assert top1(emb[2]) != 2, backend


# ---------------------------------------------------------------------------
# Accounting: live/tombstone stats and live-only per-segment payload
# ---------------------------------------------------------------------------

def test_build_stats_live_and_tombstones(churned):
    r = churned["retriever"]
    stats = r.build_stats(churned["state"])
    n_live = len(churned["live_ids"])
    assert stats["live_docs"] == n_live, (churned["backend"], stats)
    assert stats["tombstoned_docs"] >= len(DEAD), (churned["backend"], stats)
    total = stats["live_docs"] + stats["tombstoned_docs"]
    assert stats["tombstone_frac"] == pytest.approx(
        stats["tombstoned_docs"] / total)
    # hnsw grows in place (one capacity-padded graph segment); everyone
    # else appends one immutable segment per add
    min_segments = 1 if churned["backend"] == "hnsw" else 2
    assert stats["segments"] >= min_segments

    stats_c = r.build_stats(churned["state_compact"])
    assert stats_c["live_docs"] == n_live
    assert stats_c["tombstoned_docs"] == 0
    assert stats_c["segments"] == 1


def test_storage_reports_per_segment_live_payload(churned, data):
    r = churned["retriever"]
    stor = r.storage_bytes(churned["state"])
    if churned["backend"] == "cascade":
        # cascade reports per-stage totals; each member accounts its own
        # segments internally
        assert any(k.startswith("stage_") for k in stor), stor
    else:
        seg_keys = [k for k in stor if k.startswith("segment_")]
        assert seg_keys, stor
        assert stor["payload"] == sum(stor[k] for k in seg_keys)

    # live-only accounting: deleting shrinks payload with no other change
    query = churned["query"]
    del query  # unused; keep fixture ordering explicit
    r2 = Retriever(_cfg(churned["backend"]))
    st = r2.build(jax.random.PRNGKey(0), _slice(data, 0, 64))
    st = r2.add(st, _slice(data, 64, 80))
    before = r2.storage_bytes(st)["payload"]
    st = r2.delete(st, np.arange(20))
    after = r2.storage_bytes(st)["payload"]
    assert after < before, (churned["backend"], before, after)


# ---------------------------------------------------------------------------
# Persistence: segmented round-trip + version gates
# ---------------------------------------------------------------------------

def test_save_load_roundtrip_segmented(churned, tmp_path):
    r = churned["retriever"]
    path = r.save(str(tmp_path / "seg_idx"), churned["state"])
    loaded = r.load(path)
    s2, i2 = r.search(loaded, churned["query"], k=10)
    assert np.array_equal(np.asarray(s2), churned["scores"])
    assert np.array_equal(np.asarray(i2), churned["ids"])


def test_load_rejects_future_format_version(churned, tmp_path):
    r = churned["retriever"]
    path = r.save(str(tmp_path / "seg_idx"), churned["state"])
    with np.load(path) as z:
        payload = {k: z[k] for k in z.files}
    payload["format_version"] = np.asarray(99, np.int64)
    np.savez(path, **payload)
    with pytest.raises(ValueError, match="format version 99"):
        r.load(path)


def test_load_rejects_non_index_file(tmp_path):
    path = str(tmp_path / "junk.npz")
    np.savez(path, stuff=np.zeros(3))
    with pytest.raises(ValueError, match="no 'backend' key"):
        Retriever(_cfg("flat")).load(path)


# ---------------------------------------------------------------------------
# Serving: interleaved mutations never mint a recompile off the ladder
# ---------------------------------------------------------------------------

def test_live_session_mutations_keep_ladder_rung_set(data):
    from repro.serving import LiveIndexSession, ServeConfig

    r = Retriever(HPCConfig(k=32, p=80.0, backend="flat", kmeans_iters=4,
                            kmeans_restarts=2, rerank=16))
    state = r.build(jax.random.PRNGKey(0), _slice(data, 0, 60))
    sess = LiveIndexSession(r, state,
                            ServeConfig(max_batch=4, top_k=5,
                                        guard_recompiles=True,
                                        max_wait_ms=1.0))
    qe = np.asarray(data.query_patches)
    qm = np.asarray(data.query_mask)
    qs = np.asarray(data.query_salience)
    try:
        sess.warm_shapes(qe[0], qm[0], qs[0])
        sess.server.reset_stats()
        for i in range(6):
            sess.query(qe[i], qm[i], qs[i])
            if i == 1:
                sess.add(_slice(data, 60, 70))          # ids 60..69
            if i == 2:
                sess.delete(np.array([0, 5, 63]))
            if i == 3:
                sess.add(_slice(data, 70, 71),
                         doc_ids=np.array([7]))         # upsert doc 7
            if i == 4:
                sess.compact()
        _, ids = sess.query(qe[6], qm[6], qs[6])
        assert not ({0, 5, 63} & set(int(x) for x in np.asarray(ids)))
        # the compiled rung set after adds/deletes/upsert/compact is
        # exactly a subset of the padding ladder — mutations swap index
        # state without minting a single off-ladder signature
        sentry = sess.server.recompile_sentry
        assert sentry.signatures, "sentry saw no traffic"
        for key in sentry.signatures:
            assert key[0] in sess.server.ladder, (key, sess.server.ladder)
        # the state-shape registry stays pow2-bucketed and bounded
        assert len(sess.state_signatures()) <= 6
    finally:
        sess.close()


def test_load_reads_v1_monolithic_file(data, tmp_path):
    # a v1 file is one saved before the format_version field existed:
    # monolithic state, no "format_version" / "segments" keys
    r = Retriever(_cfg("flat"))
    st = r.build(jax.random.PRNGKey(0), _slice(data, 0, 32))
    path = r.save(str(tmp_path / "v1_idx"), st)
    with np.load(path) as z:
        payload = {k: z[k] for k in z.files if k != "format_version"}
    np.savez(path, **payload)
    loaded = r.load(path)
    q = Query(data.query_patches, data.query_mask, data.query_salience)
    s0, i0 = r.search(st, q, k=5)
    s1, i1 = r.search(loaded, q, k=5)
    assert np.array_equal(np.asarray(s0), np.asarray(s1))
    assert np.array_equal(np.asarray(i0), np.asarray(i1))
