"""Retriever API v1: the `IndexBackend` contract + string-keyed registry.

A backend owns ONE primary search structure (exhaustive flat scan, IVF
routing, Hamming scan, ...) behind four methods over pytree state:

    build(key, corpus, cfg)             -> RetrieverState
    search(state, query, *, k, scan)    -> (scores (B, k), doc_ids (B, k))
    storage_bytes(state)                -> {"payload": ..., ...}
    save(path, state) / load(path)      -> RetrieverState

plus `shard_specs(state)` (logical-axis specs so the corpus dimension
shards over the mesh — see repro/dist/sharding.py) and the optional
*search-stage* entry point

    search_candidates(state, query, candidate_ids, *, k, scan)

which scores only a (B, P) per-query id pool — the composable-stage
contract the `cascade` backend chains (Hamming prefilter -> ADC scan ->
float rerank). Everything shared between backends — codebook training,
corpus quantization, doc/query-side pruning, candidate rerank — lives in
the `Retriever` facade (retriever.py) or in the helpers below, so a new
backend is one file:

    @register_backend("my_index")
    class MyBackend(IndexBackend):
        def build(self, key, corpus, cfg): ...
        def search(self, state, query, *, k, scan=None): ...
        def storage_bytes(self, state): ...

See docs/api.md for the full contract.
"""
from __future__ import annotations

import dataclasses
import inspect
import os
import warnings
import zlib
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import index as index_mod
from repro.core import pruning
from repro.core import quantization as quant
from repro.dist.sharding import Sharder, is_logical_spec
from repro.retrieval.config import HPCConfig

Array = jax.Array

# On-disk npz manifest version (IndexBackend.save/load). History:
#   1 — monolithic states, no version key (PR 4; absence of the key
#       identifies a v1 file, which still loads)
#   2 — adds `format_version` + the `segments` count for segmented
#       LSM states (this version reads v1 files unchanged)
#   3 — crash-safe writes (tmp file + fsync + atomic rename) and a
#       per-leaf crc32 `checksums` array; load verifies every leaf and
#       names the corrupt array instead of returning silently-bad data
#       (this version reads v1/v2 files unchanged — they carry no
#       checksums to verify)
FORMAT_VERSION = 3


def leaf_crc32(arr) -> int:
    """crc32 of an array's raw bytes (shape/dtype ride in the npz header)."""
    return zlib.crc32(np.ascontiguousarray(arr).tobytes()) & 0xFFFFFFFF


def fsync_dir(dirname: str) -> None:
    """fsync a directory so a just-renamed file survives power loss.

    Best-effort: some filesystems refuse O_RDONLY fsync on directories;
    the rename itself is still atomic there."""
    try:
        fd = os.open(dirname or ".", os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def code_dtype(k: int):
    """Dtype of centroid-index codes for a K-entry codebook.

    The single source of truth for code width: build AND query sides must
    agree (v0 quantized queries to uint16 while building uint8 corpora).
    """
    return jnp.uint8 if k <= 256 else jnp.uint16


# ---------------------------------------------------------------------------
# Data carriers (all pytrees)
# ---------------------------------------------------------------------------

class Corpus(NamedTuple):
    """Doc-side inputs: (N, Md, D) embeddings, (N, Md) mask/salience."""
    embeddings: Array
    mask: Array
    salience: Array


class Query(NamedTuple):
    """Query-side inputs: (B, Mq, D) embeddings, (B, Mq) mask/salience."""
    embeddings: Array
    mask: Array
    salience: Array


class RetrieverState(NamedTuple):
    """Built index state (a pytree — shardable/checkpointable).

    `backend_state` is the single tagged backend structure (the tag is its
    Python type — FlatIndex, IVFState, HammingState, FloatFlatIndex), which
    replaces v0's four-way Optional union. `rerank_codes`/`rerank_mask`
    hold the unpruned quantized corpus for the facade's rerank stage.
    """

    codebook: Array
    backend_state: Any
    rerank_codes: Array
    rerank_mask: Array

    # v0 `HPCIndex` compatibility accessors -------------------------------
    #
    # DEPRECATED since PR 7 (scheduled for removal in v2.0): read
    # `state.backend_state` and dispatch on its type instead. These
    # properties predate the tagged-union state and only resolve the four
    # v0 structures (a `cascade` state returns None from all of them).
    # The frozen-v0 parity tests keep exercising them until removal.

    @property
    def flat(self) -> Optional[index_mod.FlatIndex]:
        """Deprecated v0 accessor — use `backend_state` (removal: v2.0)."""
        s = self.backend_state
        return s if isinstance(s, index_mod.FlatIndex) else None

    @property
    def float_flat(self) -> Optional[index_mod.FloatFlatIndex]:
        """Deprecated v0 accessor — use `backend_state` (removal: v2.0)."""
        s = self.backend_state
        return s if isinstance(s, index_mod.FloatFlatIndex) else None

    @property
    def ivf(self) -> Optional[index_mod.IVFIndex]:
        """Deprecated v0 accessor — use `backend_state` (removal: v2.0)."""
        from repro.retrieval.ivf import IVFState
        s = self.backend_state
        return s.index if isinstance(s, IVFState) else None

    @property
    def hamming(self) -> Optional[index_mod.HammingIndex]:
        """Deprecated v0 accessor — use `backend_state` (removal: v2.0)."""
        from repro.retrieval.hamming import HammingState
        s = self.backend_state
        return s.index if isinstance(s, HammingState) else None


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, "IndexBackend"] = {}


def _accepts_scan(fn) -> bool:
    """Does this `search` implementation take the `scan=` keyword?"""
    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):   # builtins/C callables: assume modern
        return True
    return "scan" in params or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values())


def register_backend(name: str):
    """Class decorator: `@register_backend("flat")` installs a singleton.

    `search` implementations must accept the full v1 signature
    `(state, query, *, k, scan=None)`. Legacy out-of-tree backends
    whose `search` predates the `scan=` keyword still register, but get
    one `DeprecationWarning` here and a shim that strips `scan` before
    calling them (scheduled for removal in v2.0 — accept `scan=` to
    opt into the streaming-scan knobs).
    """
    def deco(cls):
        cls.name = name
        if not _accepts_scan(cls.search):
            warnings.warn(
                f"index backend {name!r}: search() does not accept the "
                "scan= keyword; registering a compatibility shim that "
                "drops it. Add `scan=None` to the signature — the shim "
                "will be removed in v2.0.",
                DeprecationWarning, stacklevel=3)
            legacy_search = cls.search

            def search(self, state, query, *, k, scan=None):
                del scan  # legacy backend cannot use the scan knobs
                return legacy_search(self, state, query, k=k)

            search.__doc__ = legacy_search.__doc__
            cls.search = search
        _REGISTRY[name] = cls()
        return cls
    return deco


def _ensure_builtin_backends():
    """Install the built-in backends (idempotent, import-cycle safe).

    Registration normally happens when `repro.retrieval` initialises; this
    lazy hook covers callers that imported only a submodule (e.g. the
    `repro.core.pipeline` compat shim during `repro.core` package init).
    """
    from repro.retrieval import (cascade, flat, float_flat,  # noqa: F401
                                 hamming, hnsw, ivf)


def get_backend(name: str) -> "IndexBackend":
    if name not in _REGISTRY:
        _ensure_builtin_backends()
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown index backend {name!r}; available: "
            f"{sorted(_REGISTRY)}")
    return _REGISTRY[name]


def available_backends() -> Tuple[str, ...]:
    _ensure_builtin_backends()
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# Shared build stages (identical numerics to the v0 pipeline)
# ---------------------------------------------------------------------------

def kmeans_config(cfg: HPCConfig) -> quant.KMeansConfig:
    """The codebook-training config implied by an HPCConfig."""
    return quant.KMeansConfig(
        k=cfg.k, iters=cfg.kmeans_iters, seed_batch=cfg.kmeans_seed_batch,
        n_restarts=cfg.kmeans_restarts, minibatch=cfg.kmeans_minibatch)


def fit_codebook(key: Array, corpus: Corpus, cfg: HPCConfig,
                 mesh=None) -> Array:
    """Train the K-Means codebook on valid patches only.

    Invalid rows are replaced by resampled valid rows so Lloyd sees real
    data (zero vectors would otherwise form their own cluster). With a
    `mesh`, training runs through the sharded k-means
    (core/distributed.py): points sharded over the corpus axes, per-cluster
    stats psum-reduced — same seeds and algorithm as the single-host path.
    """
    d = corpus.embeddings.shape[-1]
    flat = corpus.embeddings.reshape(-1, d)
    flat_mask = corpus.mask.reshape(-1)
    valid_idx = jnp.argsort(~flat_mask, stable=True)  # valid rows first
    n_valid = jnp.sum(flat_mask)
    gather_idx = jnp.where(
        jnp.arange(flat.shape[0]) < n_valid,
        valid_idx,
        valid_idx[jnp.mod(jnp.arange(flat.shape[0]),
                          jnp.maximum(n_valid, 1))])
    train_x = flat[gather_idx]
    if mesh is not None:
        from repro.core import distributed as dist
        codebook, _ = dist.sharded_kmeans_fit(mesh, key, train_x,
                                              kmeans_config(cfg))
    else:
        codebook, _ = quant.kmeans_fit(key, train_x, kmeans_config(cfg))
    return codebook


def encode_corpus(key: Array, corpus: Corpus, cfg: HPCConfig, mesh=None
                  ) -> Tuple[Array, Array, Array, Array, Array]:
    """Shared offline stages for all code-based backends.

    Splits the key exactly like v0 `build_index` (codebook key first, the
    remainder free for the backend's own structure, e.g. IVF routing),
    trains the codebook, quantizes the full corpus (the rerank structure),
    and applies doc-side pruning for the primary structure. Assignment runs
    through the Pallas kernel on TPU devices (no (N*Md, K) distance
    matrix) and the canonical jnp form elsewhere. With a `mesh`, codebook
    training and corpus quantization run sharded over the mesh's corpus
    axes.

    Returns (struct_key, codebook, codes_full, codes, mask).
    """
    k_cb, k_struct = jax.random.split(key)
    codebook = fit_codebook(k_cb, corpus, cfg, mesh=mesh)
    if mesh is not None:
        from repro.core import distributed as dist
        codes_full = dist.sharded_quantize(mesh, corpus.embeddings, codebook,
                                           code_dtype(cfg.k))       # (N, Md)
    else:
        codes_full = quant.quantize(corpus.embeddings, codebook,
                                    code_dtype=code_dtype(cfg.k),
                                    impl="auto")                    # (N, Md)
    if cfg.prune_side in ("doc", "both"):
        codes, _, mask, _ = pruning.prune_topp_codes(
            codes_full, corpus.salience, corpus.mask, p=cfg.p)
    else:
        codes, mask = codes_full, corpus.mask
    return k_struct, codebook, codes_full, codes, mask


def encode_delta(codebook: Array, delta: Corpus, cfg: HPCConfig
                 ) -> Tuple[Array, Array, Array]:
    """Encode a corpus delta against an EXISTING codebook (no refit).

    The online counterpart of `encode_corpus`: quantizes the delta's
    patches with the codebook the index was built with and applies the
    same doc-side pruning policy, so an appended segment is scored on
    exactly the representation a from-scratch build would give those
    docs. Returns (codes_full, codes, mask) — full codes feed the rerank
    rows, pruned codes/mask feed the primary structure.
    """
    k = codebook.shape[0]
    codes_full = quant.quantize(delta.embeddings, codebook,
                                code_dtype=code_dtype(k), impl="auto")
    if cfg.prune_side in ("doc", "both"):
        codes, _, mask, _ = pruning.prune_topp_codes(
            codes_full, delta.salience, delta.mask, p=cfg.p)
    else:
        codes, mask = codes_full, delta.mask
    return codes_full, codes, mask


@partial(jax.jit, donate_argnums=0)
def _write_rows(buffers, rows, at):
    """`rows` written into `buffers` from row `at` on, in place."""
    return [jax.lax.dynamic_update_slice_in_dim(b, r.astype(b.dtype), at, 0)
            for b, r in zip(buffers, rows)]


# ---------------------------------------------------------------------------
# Backend base class
# ---------------------------------------------------------------------------

class IndexBackend:
    """Contract every index backend implements (see module docstring)."""

    name: str = "?"
    # True -> the backend's scores are exact late-interaction scores over
    # raw embeddings; the facade skips the quantized rerank stage.
    exact_scores: bool = False
    # True -> `search` is an exhaustive scan of the corpus axis: run on a
    # corpus shard it returns that shard's exact top-k under global ids,
    # which is what `Retriever.search_sharded` merges.
    exhaustive: bool = False

    # -- required -----------------------------------------------------------

    def build(self, key: Array, corpus: Corpus, cfg: HPCConfig,
              mesh=None) -> RetrieverState:
        """Offline indexing. `mesh` (optional) runs the shared encode
        stages (codebook fit + corpus quantization) sharded over it."""
        raise NotImplementedError

    def search(self, state: RetrieverState, query: Query, *, k: int,
               scan=None) -> Tuple[Array, Array]:
        """Candidate search -> (scores (B, k), doc_ids (B, k)).

        `scan` (a `repro.core.scan.ScanConfig`, or None for defaults)
        selects the streaming-scan block size and block-scorer impl; all
        built-in backends route their scoring through the blocked
        score+top-k engine in core/scan.py, so no search path ever
        materialises an O(N * Mq) intermediate.

        Sentinel contract: a backend whose structure can surface fewer
        than k valid documents (ivf with sparse probed buckets, hnsw with
        a beam smaller than k reachable nodes, any backend asked for
        k > N) MUST fill the tail rows with doc_id -1 and NEG_INF-or-
        below scores (for hamming's int32 scores: the int32 minimum).
        Consumers — the facade rerank, benchmarks, hit/recall
        accounting — must ignore `id < 0` rows rather than treating them
        as real documents.
        """
        raise NotImplementedError

    def search_candidates(self, state: RetrieverState, query: Query,
                          candidate_ids: Array, *, k: int,
                          scan=None) -> Tuple[Array, Array]:
        """Score only a (B, P) per-query candidate pool -> (B, k) top-k.

        The composable search-stage entry point: `candidate_ids[b]` lists
        the corpus positions query `b` may match (typically a coarser
        stage's output ids); -1 marks empty pool slots and is never
        scored. Output rows follow the same sentinel contract as
        `search` — with fewer than k valid candidates (including k > P)
        the tail rows carry doc_id -1 and sentinel scores. Cost must be
        O(B * P), never O(N): implementations route through the scan
        engine's per-query-candidates layout, no full-corpus gather.

        `search(state, query, k=k)` is semantically this method with
        `candidate_ids=None` (the whole corpus as the pool). Backends
        whose structure already does its own candidate routing (ivf,
        hnsw) may decline by raising NotImplementedError — stage
        composition then excludes them.
        """
        if candidate_ids is None:
            return self.search(state, query, k=k, scan=scan)
        raise NotImplementedError(
            f"backend {self.name!r} does not support candidate-restricted "
            "search (search_candidates); use flat/float_flat/hamming as "
            "cascade stages")

    # -- mutation (segmented LSM store — docs/design.md §9) ------------------
    #
    # A built state starts monolithic (bit-identical to the pre-mutation
    # format); the first `add`/`delete` normalizes it into a
    # `SegmentedState` — segment 0 wraps the existing structure zero-copy.
    # `add` appends one immutable pow2-capacity segment encoded with the
    # EXISTING codebook; `delete` flips live bits (tombstones honored by
    # every search path via the valid-mask contract); `compact` gathers
    # the live docs back into a single segment. Rerank rows are indexed
    # by GLOBAL doc id throughout, so the facade's rerank never changes.

    def _segmented(self, state: RetrieverState
                   ) -> Optional[index_mod.SegmentedState]:
        """The state's SegmentedState, or None while still monolithic."""
        s = state.backend_state
        if isinstance(s, index_mod.SegmentedState):
            return s
        if self._is_wrapper(s) and isinstance(s.index,
                                              index_mod.SegmentedState):
            return s.index
        return None

    @staticmethod
    def _is_wrapper(s) -> bool:
        """Aux-carrying wrapper state (IVFState/HammingState/HNSWState)?

        NamedTuple payloads (FlatIndex, ...) also `hasattr(s, "index")` —
        the tuple method — so require a dataclass with an `index` field.
        """
        return (dataclasses.is_dataclass(s)
                and not isinstance(s, index_mod.SegmentedState)
                and any(f.name == "index" for f in dataclasses.fields(s)))

    def _set_segmented(self, state: RetrieverState,
                       seg: index_mod.SegmentedState) -> RetrieverState:
        s = state.backend_state
        if self._is_wrapper(s):
            return state._replace(
                backend_state=dataclasses.replace(s, index=seg))
        return state._replace(backend_state=seg)

    def _wrap_segment(self, state: RetrieverState
                      ) -> Tuple[Any, Array]:
        """(payload, live) wrapping the monolithic structure zero-copy."""
        s = state.backend_state
        payload = s.index if self._is_wrapper(s) else s
        return payload, index_mod.seg_doc_ids(payload) >= 0

    def _grow_rerank(self, state: RetrieverState, id_cap: int
                     ) -> RetrieverState:
        if state.rerank_codes.shape[0] >= id_cap:
            return state
        return state._replace(
            rerank_codes=index_mod.pad_dim0(state.rerank_codes, id_cap, 0),
            rerank_mask=index_mod.pad_dim0(state.rerank_mask, id_cap, False))

    def to_segmented(self, state: RetrieverState, *,
                     id_cap: Optional[int] = None) -> RetrieverState:
        """Normalize a monolithic state into single-segment form (no-op if
        already segmented). Search results are bit-identical either way —
        segment 0 IS the original structure."""
        if self._segmented(state) is not None:
            return state
        payload, live = self._wrap_segment(state)
        if id_cap is None:
            ids = np.asarray(index_mod.seg_doc_ids(payload)).reshape(-1)
            id_cap = index_mod.segment_capacity(int(ids.max(initial=-1)) + 1)
        seg = index_mod.SegmentedState(
            (payload,), (live,),
            index_mod.rebuild_pos_of_id((payload,), (live,), id_cap))
        return self._grow_rerank(self._set_segmented(state, seg), id_cap)

    # per-backend append hooks -------------------------------------------

    def _encode_delta(self, state: RetrieverState, delta: Corpus,
                      cfg: HPCConfig) -> Tuple[Array, Array, Array]:
        """(full_repr, payload_repr, payload_mask) for a delta."""
        return encode_delta(state.codebook, delta, cfg)

    def _delta_segment(self, state: RetrieverState,
                       seg: index_mod.SegmentedState, enc, delta: Corpus,
                       cfg: HPCConfig, doc_ids: Array) -> Tuple[Any, Array]:
        """(payload, live) for an append segment — backend-specific."""
        raise NotImplementedError(
            f"backend {self.name!r} does not support add()")

    def _append_segment(self, state: RetrieverState,
                        seg: index_mod.SegmentedState, enc, delta: Corpus,
                        cfg: HPCConfig, doc_ids: Array
                        ) -> index_mod.SegmentedState:
        """Default: one more immutable segment. `hnsw` overrides to grow
        its single graph segment in place (incremental insert)."""
        payload, live = self._delta_segment(state, seg, enc, delta, cfg,
                                            doc_ids)
        return index_mod.SegmentedState(
            seg.segments + (payload,), seg.live + (live,), seg.pos_of_id)

    def _rerank_delta_rows(self, enc, delta: Corpus) -> Tuple[Array, Array]:
        """Rows written into the id-indexed rerank corpus for a delta."""
        return enc[0], delta.mask

    # public mutation API -------------------------------------------------

    def add(self, state: RetrieverState, delta: Corpus, cfg: HPCConfig, *,
            doc_ids=None) -> RetrieverState:
        """Append (or upsert) documents without rebuilding. Returns the
        new state; `state` is unchanged (segments are immutable).

        doc_ids None assigns fresh ids past the largest ever used.
        Explicit ids may reuse existing ones: a live prior occurrence is
        tombstoned (upsert — the newest segment wins), a dead one stays
        dead. Duplicate ids within one delta are rejected. The delta must
        have the same patch count (Md) and embedding dim as the corpus
        the index was built on.
        """
        n_new = int(delta.embeddings.shape[0])
        if n_new == 0:
            return state
        state = self.to_segmented(state)
        seg = self._segmented(state)

        # resolve ids (host side)
        max_assigned = -1
        for payload in seg.segments:
            s_ids = np.asarray(index_mod.seg_doc_ids(payload))
            if s_ids.size:
                max_assigned = max(max_assigned, int(s_ids.max()))
        if doc_ids is None:
            ids_np = np.arange(max_assigned + 1, max_assigned + 1 + n_new,
                               dtype=np.int64)
        else:
            ids_np = np.asarray(jax.device_get(doc_ids),
                                np.int64).reshape(-1)
            if ids_np.shape[0] != n_new:
                raise ValueError(
                    f"doc_ids has {ids_np.shape[0]} entries for a "
                    f"{n_new}-doc delta")
            if (ids_np < 0).any():
                raise ValueError("doc_ids must be non-negative")
            if np.unique(ids_np).size != n_new:
                raise ValueError(
                    "duplicate doc_ids within one add() delta; split the "
                    "delta so each id appears once (newest-wins upserts "
                    "need a segment boundary between occurrences)")
        ids_j = jnp.asarray(ids_np, jnp.int32)

        # prior live occurrences of reused ids -> flattened positions now
        # (positions of existing rows are stable under append)
        pos_np = np.asarray(seg.pos_of_id)
        in_cap = ids_np < pos_np.shape[0]
        old_pos = np.where(in_cap, pos_np[np.minimum(
            ids_np, pos_np.shape[0] - 1)], -1)
        kill_pos = old_pos[old_pos >= 0]

        enc = self._encode_delta(state, delta, cfg)
        seg2 = self._append_segment(state, seg, enc, delta, cfg, ids_j)

        if kill_pos.size:  # upsert: tombstone the prior occurrence
            new_live, off = [], 0
            for payload, lv in zip(seg2.segments, seg2.live):
                size = int(np.prod(np.shape(
                    index_mod.seg_doc_ids(payload))))
                sel = kill_pos[(kill_pos >= off) & (kill_pos < off + size)]
                if sel.size:
                    lv_np = np.asarray(lv).reshape(-1).copy()
                    lv_np[sel - off] = False
                    new_live.append(jnp.asarray(
                        lv_np.reshape(np.shape(lv))))
                else:
                    new_live.append(lv)
                off += size
            seg2 = index_mod.SegmentedState(seg2.segments, tuple(new_live),
                                            seg2.pos_of_id)

        id_cap = index_mod.segment_capacity(
            max(pos_np.shape[0], int(ids_np.max()) + 1))
        seg2 = index_mod.SegmentedState(
            seg2.segments, seg2.live,
            index_mod.rebuild_pos_of_id(seg2.segments, seg2.live, id_cap))
        state = self._grow_rerank(self._set_segmented(state, seg2), id_cap)
        rc_rows, rm_rows = self._rerank_delta_rows(enc, delta)
        return state._replace(
            rerank_codes=state.rerank_codes.at[ids_j].set(
                rc_rows.astype(state.rerank_codes.dtype)),
            rerank_mask=state.rerank_mask.at[ids_j].set(
                rm_rows.astype(state.rerank_mask.dtype)))

    def build_sharded(self, key: Array, chunks, cfg: HPCConfig, *,
                      n_docs: int, mesh) -> RetrieverState:
        """Streaming sharded build; see `Retriever.build_sharded`."""
        shd = Sharder(mesh)
        share = shd.named(("corpus",), (n_docs,))
        rows_of = {d: (idx[0].start or 0, idx[0].stop or n_docs)
                   for d, idx in share.devices_indices_map(
                       (n_docs,)).items()}
        if len(set(rows_of.values())) != len(rows_of):
            raise ValueError(
                f"{n_docs} docs do not split over the corpus axes of a "
                f"{mesh.devices.size}-device mesh")

        def owner(offset: int, n: int):
            for d, (lo, hi) in rows_of.items():
                if lo <= offset and offset + n <= hi:
                    return d, offset - lo
            raise ValueError(f"chunk [{offset}, {offset + n}) is not inside "
                             f"one device's share of {n_docs} docs")

        it = iter(chunks)
        offset, first = next(it)
        if offset != 0:
            raise ValueError(f"the first chunk is at offset {offset}; the "
                             "codebook is fit on the chunk at offset 0")
        dev, _ = owner(0, first.embeddings.shape[0])
        # the one-chip build of the first chunk: the codebook, and its rows
        state0 = self.build(key, jax.device_put(first, dev), cfg)
        specs = jax.tree.leaves(self.shard_specs(state0),
                                is_leaf=is_logical_spec)
        leaves0, treedef = jax.tree.flatten(state0)
        is_row = [bool(s) and s[0] == "corpus" for s in specs]
        # every other chunk is encoded against the finished fit
        jax.block_until_ready(state0)
        local = {d: [jnp.zeros((hi - lo,) + x.shape[1:], x.dtype, device=d)
                     if r else jax.device_put(x, d)
                     for x, r in zip(leaves0, is_row)]
                 for d, (lo, hi) in rows_of.items()}
        written = np.zeros(n_docs, bool)
        in_flight = []

        def write(d, at, rows):
            bufs = [x for x, r in zip(local[d], is_row) if r]
            new = iter(_write_rows(bufs, rows, jax.device_put(
                jnp.int32(at), d)))
            local[d] = [next(new) if r else x
                        for x, r in zip(local[d], is_row)]
            # at most one chunk a device queued behind the one it runs
            in_flight.append(rows[-1])
            if len(in_flight) > len(rows_of):
                jax.block_until_ready(in_flight.pop(0))

        n = first.embeddings.shape[0]
        written[:n] = True
        write(dev, 0, [x for x, r in zip(leaves0, is_row) if r])
        del first, state0
        for offset, delta in it:
            n = delta.embeddings.shape[0]
            dev, at = owner(offset, n)
            if written[offset:offset + n].any():
                raise ValueError(f"chunk [{offset}, {offset + n}) overlaps "
                                 "one already written")
            written[offset:offset + n] = True
            delta = jax.device_put(delta, dev)
            st = jax.tree.unflatten(treedef, local[dev])
            enc = self._encode_delta(st, delta, cfg)
            ids = jax.device_put(
                np.arange(offset, offset + n, dtype=np.int32), dev)
            payload, _ = self._delta_segment(st, None, enc, delta, cfg,
                                             ids)
            s = st.backend_state
            rc, rm = self._rerank_delta_rows(enc, delta)
            rows = st._replace(
                backend_state=(dataclasses.replace(s, index=payload)
                               if self._is_wrapper(s) else payload),
                rerank_codes=rc, rerank_mask=rm)
            del st
            write(dev, at, [x[:n] for x, r in zip(
                jax.tree.leaves(rows), is_row) if r])
        if not written.all():
            raise ValueError(f"the chunks cover {int(written.sum())} of "
                             f"{n_docs} docs")
        out = []
        for j, (x, spec) in enumerate(zip(leaves0, specs)):
            shape = (n_docs,) + x.shape[1:] if is_row[j] else x.shape
            out.append(jax.make_array_from_single_device_arrays(
                shape, shd.named(tuple(spec), shape),
                [local[d][j] for d in rows_of]))
        return jax.tree.unflatten(treedef, out)

    def delete(self, state: RetrieverState, doc_ids) -> RetrieverState:
        """Tombstone documents by global id. O(total slots) host work, no
        device recompute: searches mask the docs out via the valid-mask
        contract (scores exactly NEG_INF, ids -1). Unknown or already-
        dead ids are a no-op."""
        state = self.to_segmented(state)
        seg = self._segmented(state)
        kill = np.unique(np.asarray(jax.device_get(doc_ids),
                                    np.int64).reshape(-1))
        kill = kill[kill >= 0]
        new_live, changed = [], False
        for payload, lv in zip(seg.segments, seg.live):
            s_ids = np.asarray(index_mod.seg_doc_ids(payload))
            lv_np = np.asarray(lv)
            hit = np.isin(s_ids, kill) & lv_np
            if hit.any():
                changed = True
                new_live.append(jnp.asarray(lv_np & ~hit))
            else:
                new_live.append(lv)
        if not changed:
            return state
        seg2 = index_mod.SegmentedState(
            seg.segments, tuple(new_live),
            index_mod.rebuild_pos_of_id(seg.segments, tuple(new_live),
                                        seg.pos_of_id.shape[0]))
        return self._set_segmented(state, seg2)

    def _compact_payload(self, state: RetrieverState,
                         seg: index_mod.SegmentedState, cfg: HPCConfig
                         ) -> Tuple[Any, Array]:
        """(payload, live) holding exactly the live docs — per backend."""
        raise NotImplementedError(
            f"backend {self.name!r} does not support compact()")

    def compact(self, state: RetrieverState, cfg: HPCConfig
                ) -> RetrieverState:
        """Physically drop tombstones: gather the live docs into a single
        fresh segment (ivf re-buckets through its existing centroids,
        hnsw re-inserts live nodes with their stored level draws). Doc
        ids and the id-indexed rerank rows are preserved, so search
        results over the live corpus are unchanged at full budgets."""
        state = self.to_segmented(state)
        seg = self._segmented(state)
        payload, live = self._compact_payload(state, seg, cfg)
        seg2 = index_mod.SegmentedState(
            (payload,), (live,),
            index_mod.rebuild_pos_of_id((payload,), (live,),
                                        seg.pos_of_id.shape[0]))
        return self._set_segmented(state, seg2)

    def storage_bytes(self, state: RetrieverState) -> Dict[str, int]:
        raise NotImplementedError

    # -- segmented accounting helpers ---------------------------------------

    def _seg_payload_bytes(self, payload, n_live: int) -> int:
        """Payload bytes attributable to `n_live` live docs of a segment."""
        raise NotImplementedError

    def _segmented_storage(self, state: RetrieverState,
                           seg: index_mod.SegmentedState) -> Dict[str, int]:
        """Live-docs-only payload accounting + per-segment breakdown.

        Tombstoned docs stop counting toward `payload` the moment they
        are deleted (satellite contract) — physical bytes are only freed
        at compact, but the storage *metric* tracks the live corpus.
        """
        out: Dict[str, int] = {}
        total = 0
        for i, (payload, lv) in enumerate(zip(seg.segments, seg.live)):
            ids = np.asarray(index_mod.seg_doc_ids(payload)).reshape(-1)
            n_live = int(np.sum(np.asarray(lv).reshape(-1) & (ids >= 0)))
            b = self._seg_payload_bytes(payload, n_live)
            out[f"segment_{i}_payload"] = b
            total += b
        out["payload"] = total
        cb = state.codebook
        out["codebook"] = cb.size * cb.dtype.itemsize
        return out

    def _segment_stats(self, seg: index_mod.SegmentedState
                       ) -> Dict[str, float]:
        live, tomb = seg.counts()
        return {"segments": float(seg.n_segments),
                "live_docs": float(live),
                "tombstoned_docs": float(tomb),
                "tombstone_frac": tomb / max(live + tomb, 1)}

    # -- diagnostics --------------------------------------------------------

    def build_stats(self, state: RetrieverState) -> Dict[str, float]:
        """Structure-quality stats of a built index (may sync to host).

        Backends override to expose what their build dropped or skewed
        (e.g. `ivf` reports its bucket-overflow drop rate). Default: {}
        for monolithic states; segmented states report the segment
        lifecycle counters (segments / live_docs / tombstoned_docs /
        tombstone_frac) — overriders should merge `_segment_stats` in.
        """
        seg = self._segmented(state)
        return self._segment_stats(seg) if seg is not None else {}

    def abstract_state(self, *, n: int, md: int = 16, d: int = 16,
                       k: int = 256, **knobs) -> RetrieverState:
        """Shape-only `RetrieverState` at corpus size `n` (no allocation).

        The static-analysis registration hook (docs/design.md §8): every
        leaf is a `jax.ShapeDtypeStruct`, so `repro.analysis` can trace
        `search` against a 2^20-document corpus and walk the jaxpr
        without ever building an index. `knobs` carries backend-specific
        structure parameters (ivf: n_list/n_probe, hnsw: levels/m/
        ef_search, hamming: bits) — the same statics a real build would
        bake in, so the traced program matches production.
        """
        raise NotImplementedError(
            f"backend {self.name!r} must define abstract_state to register "
            "with the jaxpr budget analyzer (repro.analysis.manifests)")

    # -- sharding -----------------------------------------------------------

    def shard_specs(self, state: RetrieverState):
        """Logical-axis spec tree matching `state` (same treedef).

        Default: shard dim 0 of every backend-state array over the
        "corpus" logical axis (documents/buckets over the mesh), keep the
        codebook replicated, shard the rerank corpus over "corpus" too.
        Backends with non-corpus leading dims override this. Segmented
        states shard per segment (each segment's dim 0 spreads over the
        mesh independently); the id->position map replicates — every
        shard resolves global ids locally.
        """
        def leaf_spec(leaf):
            nd = jnp.ndim(leaf)
            return ("corpus",) + (None,) * (nd - 1) if nd else ()
        backend_specs = jax.tree.map(leaf_spec, state.backend_state)
        if self._segmented(state) is not None:
            def fix(sp):
                return dataclasses.replace(sp, pos_of_id=(None,))
            backend_specs = (
                dataclasses.replace(backend_specs, index=fix(
                    backend_specs.index))
                if self._is_wrapper(backend_specs) else fix(backend_specs))
        return RetrieverState(
            codebook=(None, None),
            backend_state=backend_specs,
            rerank_codes=("corpus", None),
            rerank_mask=("corpus", None))

    # -- persistence --------------------------------------------------------
    #
    # One flat .npz: ordered array leaves + the backend name + an optional
    # static-aux scalar or int tuple (IVF n_probe, Hamming bits, cascade
    # (p1, p2, bits)). The treedef is NEVER serialized — it is
    # reconstructed from `state_template`, so loading an untrusted index
    # file deserializes arrays only (no pickle, no code).

    def _state_aux(self, state: RetrieverState):
        """Static aux carried by the backend state (None if stateless)."""
        return None

    def state_template(self, aux, n_segments: int = 0) -> RetrieverState:
        """Dummy-leaf state with this backend's exact pytree structure.

        `n_segments` 0 is the monolithic layout; > 0 is a SegmentedState
        with that many segments. Backends with custom state must override
        this (or save/load)."""
        raise NotImplementedError(
            f"backend {self.name!r} must define state_template (or override "
            "save/load) for persistence")

    def _n_segments(self, state: RetrieverState) -> int:
        seg = self._segmented(state)
        return seg.n_segments if seg is not None else 0

    def _template(self, aux, n_segments: int) -> RetrieverState:
        """state_template with a graceful path for legacy overrides that
        predate the n_segments parameter (monolithic-only backends)."""
        try:
            return self.state_template(aux, n_segments=n_segments)
        except TypeError:
            if n_segments:
                raise
            return self.state_template(aux)

    def save(self, path: str, state: RetrieverState) -> str:
        aux = self._state_aux(state)
        n_seg = self._n_segments(state)
        leaves, treedef = jax.tree_util.tree_flatten(state)
        template_def = jax.tree_util.tree_structure(
            self._template(aux, n_seg))
        if treedef != template_def:
            raise NotImplementedError(
                f"backend {self.name!r}: state structure {treedef} does not "
                f"match state_template {template_def}; override save/load")
        payload = {f"leaf_{i:04d}": np.asarray(leaf)
                   for i, leaf in enumerate(leaves)}
        payload["backend"] = np.array(self.name)
        payload["format_version"] = np.asarray(FORMAT_VERSION, np.int64)
        if n_seg:
            payload["segments"] = np.asarray(n_seg, np.int64)
        if aux is not None:
            payload["aux"] = np.asarray(aux, np.int64)
        # v3: per-leaf crc32, ordered like the leaf keys — load verifies
        # and names the corrupt array instead of returning bad data
        payload["checksums"] = np.asarray(
            [leaf_crc32(payload[f"leaf_{i:04d}"])
             for i in range(len(leaves))], np.uint32)
        if not path.endswith(".npz"):
            path = path + ".npz"
        # crash-safe: a SIGKILL mid-write leaves either the previous
        # complete file or a stray .tmp — never a torn index at `path`
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, **payload)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        fsync_dir(os.path.dirname(path))
        return path

    def load(self, path: str) -> RetrieverState:
        if not path.endswith(".npz"):
            path = path + ".npz"
        with np.load(path, allow_pickle=False) as z:
            if "backend" not in z.files:
                raise ValueError(
                    f"{path!r} is not a retriever index file (no 'backend' "
                    "key); it may predate the v1 retriever format — rebuild "
                    "the index with this version")
            saved = str(z["backend"])
            if saved != self.name:
                raise ValueError(
                    f"index was saved by backend {saved!r}, not {self.name!r}")
            # absence of the key marks a format-v1 file (still readable);
            # files from the future fail with a clear message instead of
            # an opaque structure mismatch
            version = (int(z["format_version"])
                       if "format_version" in z.files else 1)
            if version > FORMAT_VERSION:
                raise ValueError(
                    f"index file {path!r} has format version {version}; "
                    f"this build reads versions <= {FORMAT_VERSION} — "
                    "upgrade to load it, or re-save with this version")
            n_seg = int(z["segments"]) if "segments" in z.files else 0
            if "aux" in z.files:
                a = z["aux"]
                aux = int(a) if a.ndim == 0 else tuple(int(x) for x in a)
            else:
                aux = None
            names = sorted(n for n in z.files if n.startswith("leaf_"))
            host_leaves = [z[n] for n in names]
            if "checksums" in z.files:
                crcs = np.asarray(z["checksums"], np.uint32)
                if crcs.size != len(names):
                    raise ValueError(
                        f"index file {path!r} carries {crcs.size} checksums "
                        f"for {len(names)} arrays — truncated manifest")
                for name, arr, want in zip(names, host_leaves, crcs):
                    got = leaf_crc32(arr)
                    if got != int(want):
                        raise ValueError(
                            f"index file {path!r}: checksum mismatch on "
                            f"array {name!r} (crc32 {got:#010x} != stored "
                            f"{int(want):#010x}) — the file is corrupt; "
                            "restore from a previous complete save")
            leaves = [jnp.asarray(a) for a in host_leaves]
        treedef = jax.tree_util.tree_structure(self._template(aux, n_seg))
        if treedef.num_leaves != len(leaves):
            raise ValueError(
                f"index file has {len(leaves)} arrays, backend {self.name!r} "
                f"expects {treedef.num_leaves}")
        return jax.tree_util.tree_unflatten(treedef, leaves)
