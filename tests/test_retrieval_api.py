"""Retriever API v1 tests: registry, parity with the v0 pipeline,
save/load round-trips, sharding, and the HPCConfig deprecation shim.

The parity reference below is a frozen inline copy of the v0
`build_index`/`query`/`storage_bytes` path (core/pipeline.py at the seed),
so the refactor is pinned to be *numerically identical*, not just
plausible.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import binary as binary_mod
from repro.core import index as index_mod
from repro.core import late_interaction as li
from repro.core import pruning
from repro.core import quantization as quant
from repro.data import synthetic
from repro.retrieval import (Corpus, HPCConfig, Query, Retriever,
                             available_backends, code_dtype, get_backend)


# ---------------------------------------------------------------------------
# Frozen v0 reference (verbatim semantics of the seed pipeline)
# ---------------------------------------------------------------------------

def _legacy_build(key, doc_emb, doc_mask, doc_salience, config):
    n, md, d = doc_emb.shape
    k_cb, k_ivf = jax.random.split(key)

    if config.mode == "float":
        emb, mask = doc_emb, doc_mask
        if config.prune_side in ("doc", "both"):
            pr = pruning.prune_topp(doc_emb, doc_salience, doc_mask,
                                    p=config.p)
            emb, mask = pr.embeddings, pr.mask
        return {"codebook": jnp.zeros((1, d), doc_emb.dtype),
                "float_flat": index_mod.build_float_flat(emb, mask)}

    flat = doc_emb.reshape(-1, d)
    flat_mask = doc_mask.reshape(-1)
    valid_idx = jnp.argsort(~flat_mask, stable=True)
    n_valid = jnp.sum(flat_mask)
    gather_idx = jnp.where(
        jnp.arange(flat.shape[0]) < n_valid,
        valid_idx,
        valid_idx[jnp.mod(jnp.arange(flat.shape[0]),
                          jnp.maximum(n_valid, 1))])
    train_x = flat[gather_idx]
    codebook, _ = quant.kmeans_fit(
        k_cb, train_x,
        quant.KMeansConfig(k=config.k, iters=config.kmeans_iters))
    codes_full = quant.quantize(doc_emb, codebook,
                                code_dtype=jnp.uint8 if config.k <= 256
                                else jnp.uint16)
    if config.prune_side in ("doc", "both"):
        codes, _, mask, _ = pruning.prune_topp_codes(
            codes_full, doc_salience, doc_mask, p=config.p)
    else:
        codes, mask = codes_full, doc_mask

    out = {"codebook": codebook, "rerank_codes": codes_full,
           "rerank_mask": doc_mask}
    if config.mode == "binary":
        out["hamming"] = index_mod.build_hamming(codes, mask, config.bits)
    elif config.index == "ivf":
        out["ivf"] = index_mod.build_ivf(k_ivf, codes, mask, codebook,
                                         config.ivf)
    else:
        out["flat"] = index_mod.build_flat(codes, mask, codebook)
    return out


def _legacy_query(ix, q_emb, q_mask, q_salience, config, *, k):
    if config.prune_side in ("query", "both"):
        pr = pruning.prune_topp(q_emb, q_salience, q_mask, p=config.p)
        q_emb, q_mask = pr.embeddings, pr.mask

    n_cand = k if config.rerank == 0 else max(k, config.rerank)
    if config.mode == "float":
        scores, ids = index_mod.search_float_flat(
            ix["float_flat"], q_emb, q_mask, k=n_cand)
    elif config.mode == "binary":
        # v0 quirk: queries always quantized to uint16 (values identical)
        q_codes = quant.quantize(q_emb, ix["codebook"],
                                 code_dtype=jnp.uint16)
        scores, ids = index_mod.search_hamming(
            ix["hamming"], q_codes, q_mask, bits=config.bits, k=n_cand)
    elif config.index == "ivf":
        scores, ids = index_mod.search_ivf(
            ix["ivf"], q_emb, q_mask, n_probe=config.ivf.n_probe, k=n_cand)
    else:
        scores, ids = index_mod.search_flat(ix["flat"], q_emb, q_mask,
                                            k=n_cand)

    if config.rerank and config.mode != "float":
        cand_codes = ix["rerank_codes"][ids]
        cand_mask = ix["rerank_mask"][ids]

        def rerank_one(qi, qmi, codes, msk):
            return li.quantized_maxsim(qi[None], qmi[None], codes, msk,
                                       ix["codebook"])[0]

        re_scores = jax.vmap(rerank_one)(q_emb, q_mask, cand_codes,
                                         cand_mask)
        re_scores = jnp.where(ids >= 0, re_scores, li.NEG_INF)
        top_s, top_i = jax.lax.top_k(re_scores, k)
        return top_s, jnp.take_along_axis(ids, top_i, axis=1)
    return scores[:, :k], ids[:, :k]


def _legacy_storage(ix, config):
    out = {}
    if config.mode == "float":
        e = ix["float_flat"].embeddings
        out["payload"] = e.size * e.dtype.itemsize
    elif config.mode == "binary":
        n_codes = int(ix["hamming"].codes.size)
        out["payload"] = binary_mod.packed_nbytes(n_codes, config.bits)
        out["codebook"] = (ix["codebook"].size
                           * ix["codebook"].dtype.itemsize)
    else:
        codes = (ix["flat"].codes if "flat" in ix
                 else ix["ivf"].bucket_codes)
        out["payload"] = codes.size * codes.dtype.itemsize
        out["codebook"] = (ix["codebook"].size
                           * ix["codebook"].dtype.itemsize)
    return out


# ---------------------------------------------------------------------------
# Fixtures / configs
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def data():
    key = jax.random.PRNGKey(0)
    spec = synthetic.CorpusSpec(n_docs=128, n_queries=16, n_patches=12,
                                n_q_patches=4, dim=24, n_topics=8,
                                dup_per_doc=2)
    return synthetic.make_retrieval_corpus(key, spec)


CONFIGS = {
    "float_flat": HPCConfig(backend="float_flat", p=60.0, prune_side="doc",
                            kmeans_iters=5),
    "flat": HPCConfig(k=32, p=60.0, backend="flat", prune_side="doc",
                      kmeans_iters=8, rerank=12),
    "ivf": HPCConfig(k=32, p=100.0, backend="ivf", prune_side="none",
                     kmeans_iters=8, rerank=12,
                     ivf=index_mod.IVFConfig(n_list=8, n_probe=4, iters=5)),
    "hamming": HPCConfig(k=32, p=60.0, backend="hamming", prune_side="doc",
                         kmeans_iters=8),
}


def _corpus(data):
    return Corpus(data.doc_patches, data.doc_mask, data.doc_salience)


def _queries(data):
    return Query(data.query_patches, data.query_mask, data.query_salience)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_registry_resolves_all_backends():
    assert available_backends() == ("cascade", "flat", "float_flat",
                                    "hamming", "hnsw", "ivf")
    for name in available_backends():
        b = get_backend(name)
        assert b.name == name
        assert get_backend(name) is b          # singleton


def test_registry_unknown_backend_raises():
    with pytest.raises(KeyError, match="scann"):
        get_backend("scann")


def test_out_of_tree_backend_with_legacy_search_signature(data):
    """An out-of-tree backend written against the pre-scan contract
    search(state, query, *, k) must keep working: registration now warns
    once (DeprecationWarning) and installs a kwargs-stripping shim, so
    the facade can always pass `scan=` without sniffing signatures."""
    from repro.retrieval import base as base_mod

    with pytest.warns(DeprecationWarning, match="scan"):
        @base_mod.register_backend("legacy_sig")
        class LegacyBackend(base_mod.IndexBackend):
            exact_scores = True

            def build(self, key, corpus, cfg, mesh=None):
                n = corpus.embeddings.shape[0]
                return base_mod.RetrieverState(
                    jnp.zeros((1, 1)), jnp.arange(n, dtype=jnp.int32),
                    jnp.zeros((n, 1), jnp.uint8), jnp.zeros((n, 1), bool))

            def search(self, state, query, *, k):      # no `scan` kwarg
                b = query.embeddings.shape[0]
                ids = jnp.tile(state.backend_state[None, :k], (b, 1))
                return jnp.zeros((b, k)), ids

            def storage_bytes(self, state):
                return {}

    try:
        r = Retriever(HPCConfig(backend="legacy_sig"))
        state = r.build(jax.random.PRNGKey(0), _corpus(data))
        scores, ids = r.search(state, _queries(data), k=3)
        assert ids.shape == (data.query_patches.shape[0], 3)
        np.testing.assert_array_equal(np.asarray(ids[0]), [0, 1, 2])
        # the shim accepts (and drops) the scan kwarg explicitly too
        from repro.core.scan import ScanConfig
        s2, i2 = get_backend("legacy_sig").search(
            state, _queries(data), k=3, scan=ScanConfig(block_docs=7))
        np.testing.assert_array_equal(np.asarray(i2), np.asarray(ids))
    finally:
        base_mod._REGISTRY.pop("legacy_sig", None)


def test_modern_backend_registration_does_not_warn(recwarn):
    """Backends that accept scan= (or **kwargs) register silently."""
    from repro.retrieval import base as base_mod

    @base_mod.register_backend("modern_sig")
    class ModernBackend(base_mod.IndexBackend):
        def search(self, state, query, *, k, scan=None):
            raise NotImplementedError

        def storage_bytes(self, state):
            return {}

    try:
        assert not [w for w in recwarn
                    if issubclass(w.category, DeprecationWarning)]
    finally:
        base_mod._REGISTRY.pop("modern_sig", None)


def test_code_dtype_boundary():
    assert code_dtype(128) == jnp.uint8
    assert code_dtype(256) == jnp.uint8
    assert code_dtype(257) == jnp.uint16
    assert code_dtype(512) == jnp.uint16


# ---------------------------------------------------------------------------
# Parity with the frozen v0 path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", list(CONFIGS))
def test_parity_with_v0_pipeline(data, name):
    cfg = CONFIGS[name]
    key = jax.random.PRNGKey(7)
    r = Retriever(cfg)

    state = r.build(key, _corpus(data))
    s_new, i_new = r.search(state, _queries(data), k=5)

    legacy_ix = _legacy_build(key, data.doc_patches, data.doc_mask,
                              data.doc_salience, cfg)
    # jitted, as `Retriever.search` is: run op by op, XLA's CPU backend
    # rounds the rerank's Mq-sum differently (1 ulp) from the fused form
    s_old, i_old = jax.jit(lambda q, m, s: _legacy_query(
        legacy_ix, q, m, s, cfg, k=5))(data.query_patches, data.query_mask,
                                       data.query_salience)

    np.testing.assert_array_equal(np.asarray(i_new), np.asarray(i_old))
    np.testing.assert_allclose(np.asarray(s_new), np.asarray(s_old),
                               rtol=0, atol=0)
    assert r.storage_bytes(state) == _legacy_storage(legacy_ix, cfg)


def test_pipeline_wrappers_match_retriever(data):
    """The v0 entry points in core/pipeline.py are exact wrappers."""
    from repro.core import pipeline as pipe
    cfg = CONFIGS["flat"]
    key = jax.random.PRNGKey(3)
    ix = pipe.build_index(key, data.doc_patches, data.doc_mask,
                          data.doc_salience, cfg)
    s_w, i_w = pipe.query(ix, data.query_patches, data.query_mask,
                          data.query_salience, cfg, k=5)
    r = Retriever(cfg)
    state = r.build(key, _corpus(data))
    s_r, i_r = r.search(state, _queries(data), k=5)
    np.testing.assert_array_equal(np.asarray(i_w), np.asarray(i_r))
    np.testing.assert_array_equal(np.asarray(s_w), np.asarray(s_r))
    assert pipe.storage_bytes(ix, cfg) == r.storage_bytes(state)
    # v0 compat accessors on the tagged state
    assert ix.flat is not None
    assert ix.ivf is None and ix.hamming is None and ix.float_flat is None


# ---------------------------------------------------------------------------
# save / load
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", list(CONFIGS))
def test_save_load_roundtrip(data, name, tmp_path):
    cfg = CONFIGS[name]
    key = jax.random.PRNGKey(11)
    r = Retriever(cfg)
    state = r.build(key, _corpus(data))
    path = r.save(str(tmp_path / f"{name}_idx"), state)

    restored = r.load(path)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    s0, i0 = r.search(state, _queries(data), k=5)
    s1, i1 = r.search(restored, _queries(data), k=5)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


def test_load_rejects_wrong_backend(data, tmp_path):
    r = Retriever(CONFIGS["flat"])
    state = r.build(jax.random.PRNGKey(0), _corpus(data))
    path = r.save(str(tmp_path / "idx"), state)
    with pytest.raises(ValueError, match="saved by backend"):
        Retriever(CONFIGS["hamming"]).load(path)


# ---------------------------------------------------------------------------
# sharding
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", list(CONFIGS))
def test_shard_places_state_and_preserves_results(data, name):
    cfg = CONFIGS[name]
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    r = Retriever(cfg)
    state = r.build(jax.random.PRNGKey(5), _corpus(data))
    s0, i0 = r.search(state, _queries(data), k=5)

    sharded = r.shard(state, mesh)
    # every leaf got a mesh placement
    for leaf in jax.tree.leaves(sharded):
        assert leaf.sharding.mesh.shape == mesh.shape
    s1, i1 = r.search(sharded, _queries(data), k=5)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), atol=1e-5)


@pytest.mark.parametrize("name", ["flat", "ivf", "hamming"])
def test_build_on_1dev_mesh_matches_single_host(data, name):
    """Acceptance: a 1-device-mesh sharded build (codebook through the
    distributed k-means, quantization shard-mapped) must reproduce the
    single-host codebook within tolerance and the same search answers."""
    cfg = CONFIGS[name]
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    r = Retriever(cfg)
    st_mesh = r.build(jax.random.PRNGKey(5), _corpus(data), mesh=mesh)
    st_local = r.build(jax.random.PRNGKey(5), _corpus(data))
    np.testing.assert_allclose(np.asarray(st_mesh.codebook),
                               np.asarray(st_local.codebook), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(st_mesh.rerank_codes),
                                  np.asarray(st_local.rerank_codes))
    s_m, i_m = r.search(st_mesh, _queries(data), k=5)
    s_l, i_l = r.search(st_local, _queries(data), k=5)
    np.testing.assert_allclose(np.asarray(s_m), np.asarray(s_l), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(i_m), np.asarray(i_l))


def test_shard_specs_corpus_axis(data):
    """The primary structure shards over the corpus logical axis."""
    r = Retriever(CONFIGS["flat"])
    state = r.build(jax.random.PRNGKey(5), _corpus(data))
    specs = r.backend.shard_specs(state)
    assert specs.backend_state.codes == ("corpus", None)
    assert specs.backend_state.codebook == (None, None)
    assert specs.rerank_codes == ("corpus", None)
    assert specs.codebook == (None, None)


# ---------------------------------------------------------------------------
# HPCConfig deprecation shim
# ---------------------------------------------------------------------------

def test_config_mode_index_derive_backend(monkeypatch):
    from repro.retrieval import config as config_mod

    # the deprecation warns once per process; reset the flag per assert
    monkeypatch.setattr(config_mod, "_mode_index_warned", False)
    with pytest.warns(DeprecationWarning, match="removed in v2.0"):
        cfg = HPCConfig(mode="binary")
    assert cfg.backend == "hamming"
    monkeypatch.setattr(config_mod, "_mode_index_warned", False)
    with pytest.warns(DeprecationWarning):
        cfg = HPCConfig(mode="quantized", index="ivf")
    assert cfg.backend == "ivf"
    monkeypatch.setattr(config_mod, "_mode_index_warned", False)
    with pytest.warns(DeprecationWarning):
        cfg = HPCConfig(mode="float")
    assert cfg.backend == "float_flat"


def test_config_mode_index_warns_once_per_process(monkeypatch, recwarn):
    from repro.retrieval import config as config_mod

    monkeypatch.setattr(config_mod, "_mode_index_warned", False)
    with pytest.warns(DeprecationWarning):
        HPCConfig(mode="binary")
    recwarn.clear()
    HPCConfig(mode="binary")               # second construction: silent
    assert not [w for w in recwarn
                if issubclass(w.category, DeprecationWarning)]


def test_config_backend_wins_and_populates_aliases():
    cfg = HPCConfig(backend="ivf")
    assert (cfg.mode, cfg.index) == ("quantized", "ivf")
    cfg = HPCConfig(backend="hamming")
    assert cfg.mode == "binary"
    # defaults stay quantized/flat with no warning
    cfg = HPCConfig()
    assert cfg.backend == "flat"
    assert (cfg.mode, cfg.index) == ("quantized", "flat")


def test_config_replace_keeps_backend():
    cfg = HPCConfig(backend="flat", rerank=8)
    cfg2 = dataclasses.replace(cfg, rerank=16)
    assert cfg2.backend == "flat" and cfg2.rerank == 16
