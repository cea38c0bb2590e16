"""`hamming` backend: binary codes + VPU popcount MaxSim (paper §III-D).

Queries are quantized to centroid indices with the SAME code dtype the
corpus was built with (`code_dtype(k)` — v0 inconsistently used uint16
for queries vs uint8 corpora). The bit width is static aux data.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import binary as binary_mod
from repro.core import index as index_mod
from repro.core import quantization as quant
from repro.retrieval.base import (Corpus, IndexBackend, Query,
                                  RetrieverState, code_dtype, encode_corpus,
                                  register_backend)
from repro.retrieval.config import HPCConfig

Array = jax.Array


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class HammingState:
    """HammingIndex + the static bit width (aux data, not a leaf)."""

    index: index_mod.HammingIndex
    bits: int

    def tree_flatten(self):
        return (self.index,), self.bits

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux)


@register_backend("hamming")
class HammingBackend(IndexBackend):
    exhaustive = True

    def build(self, key: Array, corpus: Corpus, cfg: HPCConfig,
              mesh=None) -> RetrieverState:
        _, codebook, codes_full, codes, mask = encode_corpus(
            key, corpus, cfg, mesh=mesh)
        ham = index_mod.build_hamming(codes, mask, cfg.bits)
        return RetrieverState(
            codebook=codebook,
            backend_state=HammingState(ham, cfg.bits),
            rerank_codes=codes_full,
            rerank_mask=corpus.mask)

    def _q_codes(self, state: RetrieverState, query: Query) -> Array:
        return quant.quantize(query.embeddings, state.codebook,
                              code_dtype=code_dtype(
                                  1 << state.backend_state.bits))

    def search(self, state: RetrieverState, query: Query, *, k: int,
               scan=None) -> Tuple[Array, Array]:
        s = state.backend_state
        q_codes = self._q_codes(state, query)
        seg = self._segmented(state)
        if seg is not None:
            return index_mod.search_hamming_segmented(
                seg, q_codes, query.mask, bits=s.bits, k=k, scan=scan)
        return index_mod.search_hamming(s.index, q_codes, query.mask,
                                        bits=s.bits, k=k, scan=scan)

    def search_candidates(self, state: RetrieverState, query: Query,
                          candidate_ids, *, k: int,
                          scan=None) -> Tuple[Array, Array]:
        if candidate_ids is None:
            return self.search(state, query, k=k, scan=scan)
        s = state.backend_state
        q_codes = self._q_codes(state, query)
        seg = self._segmented(state)
        if seg is not None:
            return index_mod.search_hamming_segmented_candidates(
                seg, q_codes, query.mask, candidate_ids,
                bits=s.bits, k=k, scan=scan)
        return index_mod.search_hamming_candidates(
            s.index, q_codes, query.mask, candidate_ids,
            bits=s.bits, k=k, scan=scan)

    # -- mutation hooks ------------------------------------------------------

    def _delta_segment(self, state, seg, enc, delta, cfg, doc_ids):
        _, codes, mask = enc
        return index_mod.make_hamming_segment(
            codes, mask, state.backend_state.bits, doc_ids)

    def _compact_payload(self, state, seg, cfg):
        (codes, mask), ids = index_mod.gather_live_rows(
            seg, ("codes", "mask"))
        return index_mod.make_hamming_segment(
            codes, mask, state.backend_state.bits, ids)

    def _seg_payload_bytes(self, payload, n_live: int) -> int:
        bits = int(payload.bits)
        return binary_mod.packed_nbytes(n_live * payload.codes.shape[-1],
                                        bits)

    def storage_bytes(self, state: RetrieverState) -> Dict[str, int]:
        s = state.backend_state
        seg = self._segmented(state)
        if seg is not None:
            return self._segmented_storage(state, seg)
        n_codes = int(s.index.codes.size)
        cb = state.codebook
        return {"payload": binary_mod.packed_nbytes(n_codes, s.bits),
                "codebook": cb.size * cb.dtype.itemsize}

    def abstract_state(self, *, n: int, md: int = 16, d: int = 16,
                       k: int = 256, **knobs) -> RetrieverState:
        bits = knobs.get("bits", binary_mod.bits_for_k(k))
        sds, cdt = jax.ShapeDtypeStruct, code_dtype(1 << bits)

        def seg_payload(cap):
            return index_mod.HammingIndex(
                codes=sds((cap, md), cdt),
                mask=sds((cap, md), jnp.bool_),
                doc_ids=sds((cap,), jnp.int32),
                bits=sds((), jnp.int32))

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
            codebook=sds((k, d), jnp.float32),
            backend_state=HammingState(bs, bits),
            rerank_codes=sds((n, md), cdt),
            rerank_mask=sds((n, md), jnp.bool_))

    def _state_aux(self, state: RetrieverState):
        return state.backend_state.bits

    def state_template(self, aux, n_segments: int = 0) -> RetrieverState:
        if n_segments:
            bs = index_mod.SegmentedState(
                tuple(index_mod.HammingIndex(0, 0, 0, 0)
                      for _ in range(n_segments)),
                (0,) * n_segments, 0)
        else:
            bs = index_mod.HammingIndex(0, 0, 0, 0)
        return RetrieverState(0, HammingState(bs, aux), 0, 0)
