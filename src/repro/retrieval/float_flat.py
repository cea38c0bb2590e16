"""`float_flat` backend: uncompressed exhaustive MaxSim (ColPali-Full).

The paper's fp32 baseline — no codebook, no rerank (its scores are already
exact late-interaction scores).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import index as index_mod
from repro.core import pruning
from repro.retrieval.base import (Corpus, IndexBackend, Query,
                                  RetrieverState, register_backend)
from repro.retrieval.config import HPCConfig

Array = jax.Array


@register_backend("float_flat")
class FloatFlatBackend(IndexBackend):
    exhaustive = True
    exact_scores = True

    def build(self, key: Array, corpus: Corpus, cfg: HPCConfig,
              mesh=None) -> RetrieverState:
        # no codebook to train — `mesh` is accepted for contract parity
        # (the float corpus shards post-build via Retriever.shard)
        n, _, d = corpus.embeddings.shape
        emb, mask = corpus.embeddings, corpus.mask
        if cfg.prune_side in ("doc", "both"):
            pr = pruning.prune_topp(emb, corpus.salience, mask, p=cfg.p)
            emb, mask = pr.embeddings, pr.mask
        return RetrieverState(
            codebook=jnp.zeros((1, d), corpus.embeddings.dtype),
            backend_state=index_mod.build_float_flat(emb, mask),
            rerank_codes=jnp.zeros((n, 1), jnp.uint8),
            rerank_mask=jnp.zeros((n, 1), bool))

    def search(self, state: RetrieverState, query: Query, *, k: int,
               scan=None) -> Tuple[Array, Array]:
        seg = self._segmented(state)
        if seg is not None:
            return index_mod.search_float_flat_segmented(
                seg, query.embeddings, query.mask, k=k, scan=scan)
        return index_mod.search_float_flat(
            state.backend_state, query.embeddings, query.mask, k=k,
            scan=scan)

    def search_candidates(self, state: RetrieverState, query: Query,
                          candidate_ids, *, k: int,
                          scan=None) -> Tuple[Array, Array]:
        if candidate_ids is None:
            return self.search(state, query, k=k, scan=scan)
        seg = self._segmented(state)
        if seg is not None:
            return index_mod.search_float_flat_segmented_candidates(
                seg, query.embeddings, query.mask, candidate_ids, k=k,
                scan=scan)
        return index_mod.search_float_flat_candidates(
            state.backend_state, query.embeddings, query.mask,
            candidate_ids, k=k, scan=scan)

    # -- mutation hooks ------------------------------------------------------

    def _encode_delta(self, state, delta, cfg):
        # no codebook: the payload is the (doc-pruned) float embeddings
        emb, mask = delta.embeddings, delta.mask
        if cfg.prune_side in ("doc", "both"):
            pr = pruning.prune_topp(emb, delta.salience, mask, p=cfg.p)
            emb, mask = pr.embeddings, pr.mask
        return emb, emb, mask

    def _delta_segment(self, state, seg, enc, delta, cfg, doc_ids):
        _, emb, mask = enc
        return index_mod.make_float_flat_segment(emb, mask, doc_ids)

    def _rerank_delta_rows(self, enc, delta):
        # exact_scores backend: the facade never reranks — keep the dummy
        # placeholder rows the build writes
        n = delta.embeddings.shape[0]
        return jnp.zeros((n, 1), jnp.uint8), jnp.zeros((n, 1), bool)

    def _compact_payload(self, state, seg, cfg):
        (emb, mask), ids = index_mod.gather_live_rows(
            seg, ("embeddings", "mask"))
        return index_mod.make_float_flat_segment(emb, mask, ids)

    def _seg_payload_bytes(self, payload, n_live: int) -> int:
        e = payload.embeddings
        return n_live * e.shape[-2] * e.shape[-1] * e.dtype.itemsize

    def storage_bytes(self, state: RetrieverState) -> Dict[str, int]:
        seg = self._segmented(state)
        if seg is not None:
            out = self._segmented_storage(state, seg)
            out.pop("codebook", None)    # dummy (1, d) placeholder
            return out
        e = state.backend_state.embeddings
        return {"payload": e.size * e.dtype.itemsize}

    def abstract_state(self, *, n: int, md: int = 16, d: int = 16,
                       k: int = 256, **knobs) -> RetrieverState:
        sds = jax.ShapeDtypeStruct

        def seg_payload(cap):
            return index_mod.FloatFlatIndex(
                embeddings=sds((cap, md, d), jnp.float32),
                mask=sds((cap, md), jnp.bool_),
                doc_ids=sds((cap,), jnp.int32))

        segments = knobs.get("segments")
        if segments is not None:
            id_cap = knobs.get("id_cap",
                               index_mod.segment_capacity(sum(segments)))
            bs = index_mod.SegmentedState(
                tuple(seg_payload(c) for c in segments),
                tuple(sds((c,), jnp.bool_) for c in segments),
                sds((id_cap,), jnp.int32))
            n = id_cap
        else:
            bs = seg_payload(n)
        return RetrieverState(
            codebook=sds((1, d), jnp.float32),
            backend_state=bs,
            rerank_codes=sds((n, 1), jnp.uint8),
            rerank_mask=sds((n, 1), jnp.bool_))

    def state_template(self, aux, n_segments: int = 0) -> RetrieverState:
        if n_segments:
            bs = index_mod.SegmentedState(
                tuple(index_mod.FloatFlatIndex(0, 0, 0)
                      for _ in range(n_segments)),
                (0,) * n_segments, 0)
        else:
            bs = index_mod.FloatFlatIndex(0, 0, 0)
        return RetrieverState(0, bs, 0, 0)
