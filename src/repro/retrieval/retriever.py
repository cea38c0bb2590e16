"""`Retriever`: the facade over config-selected index backends.

Owns every stage that is backend-independent — query-side dynamic pruning
(paper §III-C), candidate over-fetch, and the rerank over the unpruned
quantized corpus (§III-E2 step 5) — and delegates the primary structure to
the backend resolved from `cfg.backend` via the registry. All state flows
through `RetrieverState` pytrees, so build/search jit, shard (see
`shard`), checkpoint and donate cleanly.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import pruning
from repro.core import scan as scan_mod
from repro.dist.sharding import Sharder, is_logical_spec
from repro.retrieval.base import (Corpus, IndexBackend, Query,
                                  RetrieverState, get_backend)
from repro.retrieval.config import HPCConfig

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class Retriever:
    """HPC-ColPali retrieval over a pluggable index backend."""

    cfg: HPCConfig

    @property
    def backend(self) -> IndexBackend:
        return get_backend(self.cfg.backend)

    # -- offline ------------------------------------------------------------

    def build(self, key: Array, corpus: Corpus, *,
              mesh: Optional[Mesh] = None) -> RetrieverState:
        """Offline indexing (paper §III-E1).

        With `mesh`, the shared encode stages run sharded: codebook
        training through the distributed k-means (points over the mesh's
        corpus axes, per-cluster stats psum-reduced) and corpus
        quantization shard-mapped over documents, with nearest-centroid
        assignment routed through the Pallas kernel on TPU devices. On a
        1-device mesh the result matches the single-host build within
        float tolerance; without `mesh` the build is bit-stable (a pure
        function of key/corpus/config).
        """
        if mesh is None:
            # keep the pre-mesh call shape so out-of-tree backends written
            # against build(key, corpus, cfg) still work for local builds
            return self.backend.build(key, corpus, self.cfg)
        return self.backend.build(key, corpus, self.cfg, mesh=mesh)

    # -- online -------------------------------------------------------------

    def _prune_query(self, query: Query) -> Query:
        """Step 2 — query-side dynamic pruning (a no-op for doc-side)."""
        if self.cfg.prune_side in ("query", "both"):
            pr = pruning.prune_topp(query.embeddings, query.salience,
                                    query.mask, p=self.cfg.p)
            return Query(pr.embeddings, pr.mask, query.salience)
        return query

    def _n_cand(self, k: int) -> int:
        """Candidates the backend returns: k, or the rerank depth if
        deeper."""
        return k if self.cfg.rerank == 0 else max(k, self.cfg.rerank)

    def search(self, state: RetrieverState, query: Query, *, k: int
               ) -> Tuple[Array, Array]:
        """Online query (paper §III-E2 steps 2-5).

        Returns (scores (B, k), doc_ids (B, k)). The stages are
        `jax.named_scope`s on the device: `search.prune`, `search.scan`
        (with `search.table` and the scan engine's `scan.*` inside) and
        `search.rerank`.
        """
        cfg, backend = self.cfg, self.backend
        with jax.named_scope("search.prune"):
            pruned = self._prune_query(query)

        # Steps 3-4 — backend candidate search (over-fetch for rerank).
        # All backends take the full v1 signature with `scan=` — legacy
        # out-of-tree backends get a kwargs-stripping shim at registration
        # (base.register_backend), so no signature sniffing here.
        with jax.named_scope("search.scan"):
            scores, ids = backend.search(state, pruned, k=self._n_cand(k),
                                         scan=cfg.scan)

        # Step 5 — rerank candidates with unpruned quantized MaxSim.
        if cfg.rerank and not backend.exact_scores:
            with jax.named_scope("search.rerank"):
                return self._rerank(state, pruned, scores, ids, k=k)
        return scores[:, :k], ids[:, :k]

    def search_sharded(self, state: RetrieverState, query: Query, *, k: int,
                       mesh: Mesh) -> Tuple[Array, Array]:
        """`search` over a state placed on `mesh` by `shard`.

        Each device scans only the documents it holds and keeps its own
        top candidates; the candidates merge over the mesh (an all-gather
        of (B, k) rows and a top-k), and the rerank scores each merged
        candidate on the device that holds its unpruned row. The whole
        search runs under `jax.shard_map`: the TPU compiler cannot split
        a Pallas kernel across devices by itself. Returns what `search`
        returns for the same state on one device, up to the order of
        equal scores. Needs a backend whose `search` is an exhaustive
        scan (`IndexBackend.exhaustive`).
        """
        cfg, backend = self.cfg, self.backend
        if not backend.exhaustive:
            raise NotImplementedError(
                f"backend {backend.name!r} routes queries through its own "
                "structure; search_sharded needs an exhaustive scan "
                "(flat, float_flat, hamming)")
        shd = Sharder(mesh)
        specs = jax.tree.map(
            lambda spec, leaf: shd.resolve(tuple(spec), jnp.shape(leaf)),
            backend.shard_specs(state), state, is_leaf=is_logical_spec)
        entry = specs.rerank_codes[0]
        if entry is None:
            raise ValueError(
                "state is not sharded over the corpus on this mesh; use "
                "search")
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        n_cand = self._n_cand(k)
        rerank = bool(cfg.rerank) and not backend.exact_scores

        def merge(scores, ids, kk):
            # one gather over all corpus axes lays the devices' rows out in
            # doc order, so equal scores still resolve to the lower id (a
            # gather per axis would put the last axis outermost)
            scores = jax.lax.all_gather(scores, axes, axis=1, tiled=True)
            ids = jax.lax.all_gather(ids, axes, axis=1, tiled=True)
            # every device contributes kk rows, so kk <= the gathered width
            top, pos = jax.lax.top_k(scores, kk)  # noqa: JAX04
            return top, jnp.take_along_axis(ids, pos, axis=1)

        def local(st, q):
            with jax.named_scope("search.prune"):
                pruned = self._prune_query(q)
            with jax.named_scope("search.scan"):
                scores, ids = merge(*backend.search(st, pruned, k=n_cand,
                                                    scan=cfg.scan), n_cand)
            if not rerank:
                return scores[:, :k], ids[:, :k]
            with jax.named_scope("search.rerank"):
                rows = st.rerank_codes.shape[0]
                row = ids - jax.lax.axis_index(axes) * rows
                mine = (ids >= 0) & (row >= 0) & (row < rows)
                row = jnp.clip(row, 0, rows - 1)
                return merge(*scan_mod.quantized_maxsim_topk(
                    pruned.embeddings, pruned.mask, st.rerank_codes[row],
                    st.rerank_mask[row], st.codebook, k=k, doc_ids=ids,
                    valid=mine, scan=cfg.scan), k)

        return jax.shard_map(local, mesh=mesh, in_specs=(specs, P()),
                             out_specs=(P(), P()),
                             check_vma=False)(state, query)

    def degrade_rungs(self, state: RetrieverState, *, k: int) -> Tuple:
        """Overload degradation rungs for serving (docs/design.md §11).

        Empty for backends without a quality-for-latency ladder; the
        cascade returns its budget halvings ending at the hamming-only
        floor (None)."""
        backend = self.backend
        if not hasattr(backend, "degrade_rungs"):
            return ()
        return backend.degrade_rungs(state, k=k)

    def search_degraded(self, state: RetrieverState, query: Query, *,
                        k: int, rung) -> Tuple[Array, Array]:
        """Degraded online query: same query-side pruning, cheaper funnel.

        `rung` comes from `degrade_rungs`. Degraded stages return their
        own (exact-enough) scores — no quantized rerank on top: the whole
        point of stepping down is shedding compute.
        """
        cfg, backend = self.cfg, self.backend
        pruned = self._prune_query(query)
        scores, ids = backend.search_degraded(state, pruned, k=k, rung=rung,
                                              scan=cfg.scan)
        return scores[:, :k], ids[:, :k]

    def _rerank(self, state: RetrieverState, query: Query, scores: Array,
                ids: Array, *, k: int) -> Tuple[Array, Array]:
        safe = jnp.maximum(ids, 0)
        cand_codes = state.rerank_codes[safe]                 # (B, r, Md)
        cand_mask = state.rerank_mask[safe]
        return scan_mod.quantized_maxsim_topk(
            query.embeddings, query.mask, cand_codes, cand_mask,
            state.codebook, k=k, doc_ids=ids, valid=ids >= 0,
            scan=self.cfg.scan)

    # -- mutation (LSM segments) ---------------------------------------------

    def add(self, state: RetrieverState, delta: Corpus, *,
            doc_ids=None) -> RetrieverState:
        """Append (or upsert) documents without rebuilding (segment append).

        The first mutation normalizes a monolithic build into segmented
        form (bit-identical search either way). With explicit `doc_ids`,
        ids already live in the index are upserted — the prior occurrence
        is tombstoned and the newest segment wins.
        """
        return self.backend.add(state, delta, self.cfg, doc_ids=doc_ids)

    def delete(self, state: RetrieverState, doc_ids) -> RetrieverState:
        """Tombstone documents by global id: they vanish from search
        results (scores NEG_INF, ids -1) without touching the payload."""
        return self.backend.delete(state, doc_ids)

    def compact(self, state: RetrieverState) -> RetrieverState:
        """Fold all segments into one and physically drop tombstones.

        Search over the live corpus is unchanged; storage and scan cost
        shrink to the live document set.
        """
        return self.backend.compact(state, self.cfg)

    # -- accounting ---------------------------------------------------------

    def storage_bytes(self, state: RetrieverState) -> Dict[str, int]:
        """Measured storage footprint of the built index (paper Table III).

        Counts the patch representation payload (the paper's metric);
        masks/ids are reported separately.
        """
        return self.backend.storage_bytes(state)

    def build_stats(self, state: RetrieverState) -> Dict[str, float]:
        """Structure-quality stats of a built index (backend-defined).

        `ivf` reports its bucket-overflow drop rate (enforced against
        `IVFConfig.max_drop_rate` at build time), `hnsw` its realised
        level-0 degree and entry level; flat scans have nothing to report.
        """
        return self.backend.build_stats(state)

    # -- persistence --------------------------------------------------------

    def save(self, path: str, state: RetrieverState) -> str:
        return self.backend.save(path, state)

    def load(self, path: str) -> RetrieverState:
        return self.backend.load(path)

    # -- distribution -------------------------------------------------------

    def shard(self, state: RetrieverState, mesh: Mesh,
              sharder: Optional[Sharder] = None) -> RetrieverState:
        """Place `state` on `mesh`, corpus dimension sharded over the mesh.

        Backends declare logical-axis specs (`shard_specs`); the "corpus"
        axis resolves over ("pod", "data", "model") with the usual
        divisibility fallback (repro/dist/sharding.py), so the same index
        shards on any mesh that divides the document count and replicates
        otherwise. Search the placed state with `search_sharded`.
        """
        shd = sharder if sharder is not None else Sharder(mesh)
        specs = self.backend.shard_specs(state)
        return jax.tree.map(
            lambda spec, leaf: jax.device_put(
                leaf, shd.named(tuple(spec), jnp.shape(leaf))),
            specs, state, is_leaf=is_logical_spec)
