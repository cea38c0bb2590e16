"""`flat` backend: exhaustive fused ADC MaxSim scan over quantized codes.

The paper's main configuration (quantized + flat): one MXU-friendly pass
over the (pruned) code corpus per query batch.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax

import jax.numpy as jnp

from repro.core import index as index_mod
from repro.retrieval.base import (Corpus, IndexBackend, Query,
                                  RetrieverState, code_dtype, encode_corpus,
                                  register_backend)
from repro.retrieval.config import HPCConfig

Array = jax.Array


@register_backend("flat")
class FlatBackend(IndexBackend):
    exhaustive = True

    def build(self, key: Array, corpus: Corpus, cfg: HPCConfig,
              mesh=None) -> RetrieverState:
        _, codebook, codes_full, codes, mask = encode_corpus(
            key, corpus, cfg, mesh=mesh)
        return RetrieverState(
            codebook=codebook,
            backend_state=index_mod.build_flat(codes, mask, codebook),
            rerank_codes=codes_full,
            rerank_mask=corpus.mask)

    def search(self, state: RetrieverState, query: Query, *, k: int,
               scan=None) -> Tuple[Array, Array]:
        seg = self._segmented(state)
        if seg is not None:
            return index_mod.search_flat_segmented(
                seg, query.embeddings, query.mask, k=k, scan=scan)
        return index_mod.search_flat(
            state.backend_state, query.embeddings, query.mask, k=k,
            scan=scan)

    def search_candidates(self, state: RetrieverState, query: Query,
                          candidate_ids, *, k: int,
                          scan=None) -> Tuple[Array, Array]:
        if candidate_ids is None:
            return self.search(state, query, k=k, scan=scan)
        seg = self._segmented(state)
        if seg is not None:
            return index_mod.search_flat_segmented_candidates(
                seg, query.embeddings, query.mask, candidate_ids, k=k,
                scan=scan)
        return index_mod.search_flat_candidates(
            state.backend_state, query.embeddings, query.mask,
            candidate_ids, k=k, scan=scan)

    # -- mutation hooks ------------------------------------------------------

    def _delta_segment(self, state, seg, enc, delta, cfg, doc_ids):
        _, codes, mask = enc
        return index_mod.make_flat_segment(codes, mask, state.codebook,
                                           doc_ids)

    def _compact_payload(self, state, seg, cfg):
        (codes, mask), ids = index_mod.gather_live_rows(
            seg, ("codes", "mask"))
        return index_mod.make_flat_segment(codes, mask, state.codebook, ids)

    def _seg_payload_bytes(self, payload, n_live: int) -> int:
        codes = payload.codes
        return n_live * codes.shape[-1] * codes.dtype.itemsize

    def storage_bytes(self, state: RetrieverState) -> Dict[str, int]:
        seg = self._segmented(state)
        if seg is not None:
            return self._segmented_storage(state, seg)
        codes = state.backend_state.codes
        cb = state.codebook
        return {"payload": codes.size * codes.dtype.itemsize,
                "codebook": cb.size * cb.dtype.itemsize}

    def abstract_state(self, *, n: int, md: int = 16, d: int = 16,
                       k: int = 256, **knobs) -> RetrieverState:
        sds, cdt = jax.ShapeDtypeStruct, code_dtype(k)

        def seg_payload(cap):
            return index_mod.FlatIndex(
                codes=sds((cap, md), cdt),
                mask=sds((cap, md), jnp.bool_),
                codebook=sds((k, d), jnp.float32),
                doc_ids=sds((cap,), jnp.int32))

        segments = knobs.get("segments")
        if segments is not None:
            # segmented layout: tuple of per-segment capacities
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
            codebook=sds((k, d), jnp.float32),
            backend_state=bs,
            rerank_codes=sds((n, md), cdt),
            rerank_mask=sds((n, md), jnp.bool_))

    def state_template(self, aux, n_segments: int = 0) -> RetrieverState:
        if n_segments:
            bs = index_mod.SegmentedState(
                tuple(index_mod.FlatIndex(0, 0, 0, 0)
                      for _ in range(n_segments)),
                (0,) * n_segments, 0)
        else:
            bs = index_mod.FlatIndex(0, 0, 0, 0)
        return RetrieverState(0, bs, 0, 0)

    def shard_specs(self, state: RetrieverState):
        specs = super().shard_specs(state)
        # the FlatIndex carries its own codebook copy — replicate it
        seg = self._segmented(state)
        if seg is not None:
            bs = specs.backend_state
            return specs._replace(backend_state=dataclasses.replace(
                bs, segments=tuple(p._replace(codebook=(None, None))
                                   for p in bs.segments)))
        return specs._replace(
            backend_state=specs.backend_state._replace(codebook=(None, None)))
