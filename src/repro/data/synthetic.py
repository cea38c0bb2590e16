"""Synthetic data generators with *planted structure*.

No datasets ship in this container (docs/design.md §1), so every benchmark runs
on controlled synthetic data where the quantities the paper measures are
well-defined:

  * `make_retrieval_corpus` — patch corpora with topic structure and graded
    relevance (3/2/1/0) so nDCG@10 / Recall@10 / MAP differences between
    ColPali-Full, PQ-Only, HPC and DistilCol are meaningful (stands in for
    ViDoRe / SEC-Filings; two presets differ in topic count, patch count
    and noise to mimic the two datasets' difficulty gap);
  * `make_page_chunk` / `make_page_queries` — the same page model made on
    the device chunk by chunk, for corpora too large to hold as floats;
  * `make_fact_corpus` — RAG corpus where each document carries an explicit
    fact set; hallucination (generated fact not present in retrieved
    context) is *exactly* measurable;
  * `make_lm_batch` — order-2 Markov token streams (learnable: loss drops
    well below ln(V));
  * `make_graph` / `make_molecule_batch` — Cora-like graphs + batched small
    graphs with community-correlated labels;
  * `make_recsys_batch` — CTR batches with a planted logistic teacher.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


# ---------------------------------------------------------------------------
# Multi-vector retrieval corpus (paper Tables I/II)
# ---------------------------------------------------------------------------

class RetrievalData(NamedTuple):
    doc_patches: Array     # (N, Md, D) float32
    doc_mask: Array        # (N, Md) bool
    doc_salience: Array    # (N, Md) float32 — synthetic attention salience
    doc_topic: Array       # (N,) int32
    query_patches: Array   # (Q, Mq, D)
    query_mask: Array      # (Q, Mq) bool
    query_salience: Array  # (Q, Mq)
    relevance: Array       # (Q, N) int32 graded 0..3


@dataclasses.dataclass(frozen=True)
class CorpusSpec:
    n_docs: int = 2048
    n_queries: int = 128
    n_patches: int = 32        # Md (paper: ~50/doc)
    n_q_patches: int = 8       # Mq
    dim: int = 128             # D (paper: 128)
    n_topics: int = 32
    patches_per_topic: int = 64
    noise: float = 0.25        # patch noise (higher -> harder corpus)
    salient_frac: float = 0.5  # fraction of patches that carry signal
    dup_per_doc: int = 3       # graded-relevant near-duplicates per query
    page_protos: int = 8       # make_page_chunk: prototypes of a page


# Presets standing in for the paper's two datasets: ViDoRe (academic pages,
# more visual variety -> more topics/prototypes, noisier) vs SEC-Filings
# (templated financial docs -> fewer topics, cleaner). Prototype counts are
# kept in the K-Means-coverable regime (topics x patches_per_topic ~ K..2K)
# because the paper's premise is that real ColPali patch embeddings are
# highly clusterable (<2% nDCG loss at K=256); STRESS below deliberately
# exceeds codebook capacity — the failure-mode ablation the paper lacks
# (EXPERIMENTS.md §Quality).
VIDORE = CorpusSpec(n_docs=2048, n_queries=128, n_topics=24,
                    patches_per_topic=10, noise=0.20, salient_frac=0.4)
SEC_FILINGS = CorpusSpec(n_docs=2048, n_queries=128, n_topics=16,
                         patches_per_topic=10, noise=0.15, salient_frac=0.4)
STRESS = CorpusSpec(n_docs=2048, n_queries=128, n_topics=48,
                    patches_per_topic=64, noise=0.30)


def make_retrieval_corpus(key: Array, spec: CorpusSpec) -> RetrievalData:
    """Build a corpus with planted graded relevance.

    Structure: each topic owns a bank of patch prototypes. A document
    samples patches from its topic bank (salient patches) mixed with
    background patches (non-salient). A query is built from a target doc's
    *salient* patches + noise. Relevance: target doc = 3, its near-duplicates
    (same prototype subset) = 2, same-topic docs = 1, rest 0.
    """
    ks = iter(jax.random.split(key, 12))
    t_centers = jax.random.normal(next(ks), (spec.n_topics, spec.dim))
    banks = (t_centers[:, None, :]
             + 0.7 * jax.random.normal(
                 next(ks), (spec.n_topics, spec.patches_per_topic, spec.dim)))

    n, md, d = spec.n_docs, spec.n_patches, spec.dim
    # group documents: target groups of (1 + dup_per_doc) near-duplicates
    group = jnp.arange(n) // (1 + spec.dup_per_doc)
    topic = group % spec.n_topics

    # per-group prototype subset (salient patches share prototypes in-group)
    n_sal = max(1, int(md * spec.salient_frac))
    proto_idx = jax.random.randint(
        next(ks), (n // (1 + spec.dup_per_doc) + 1, n_sal),
        0, spec.patches_per_topic)
    doc_proto = proto_idx[group]                               # (N, n_sal)
    sal_patches = banks[topic[:, None], doc_proto]             # (N, n_sal, D)
    bg_topic = jax.random.randint(next(ks), (n, md - n_sal), 0, spec.n_topics)
    bg_proto = jax.random.randint(next(ks), (n, md - n_sal), 0,
                                  spec.patches_per_topic)
    bg_patches = banks[bg_topic, bg_proto]                     # (N, md-n_sal, D)
    patches = jnp.concatenate([sal_patches, bg_patches], axis=1)
    patches = patches + spec.noise * jax.random.normal(next(ks), patches.shape)
    # L2 normalise (ColPali embeddings are normalised)
    patches = patches / jnp.linalg.norm(patches, axis=-1, keepdims=True)

    # synthetic attention salience: salient patches high, background low
    sal = jnp.concatenate([
        0.8 + 0.2 * jax.random.uniform(next(ks), (n, n_sal)),
        0.2 * jax.random.uniform(next(ks), (n, md - n_sal))], axis=1)
    mask = jnp.ones((n, md), bool)

    # queries from target docs (the first doc of each group)
    q_target = (jnp.arange(spec.n_queries)
                * (1 + spec.dup_per_doc)) % n                  # (Q,)
    mq = spec.n_q_patches
    pick = jax.random.randint(next(ks), (spec.n_queries, mq), 0, n_sal)
    q_patches = patches[q_target[:, None], pick]               # (Q, mq, D)
    q_patches = q_patches + spec.noise * jax.random.normal(
        next(ks), q_patches.shape)
    q_patches = q_patches / jnp.linalg.norm(q_patches, axis=-1, keepdims=True)
    q_sal = 0.5 + 0.5 * jax.random.uniform(next(ks), (spec.n_queries, mq))
    q_mask = jnp.ones((spec.n_queries, mq), bool)

    # graded relevance
    same_group = group[None, :] == group[q_target][:, None]    # (Q, N)
    same_topic = topic[None, :] == topic[q_target][:, None]
    is_target = jnp.arange(n)[None, :] == q_target[:, None]
    rel = (is_target.astype(jnp.int32) * 3
           + (same_group & ~is_target).astype(jnp.int32) * 2
           + (same_topic & ~same_group).astype(jnp.int32) * 1)
    return RetrievalData(patches.astype(jnp.float32), mask,
                         sal.astype(jnp.float32), topic.astype(jnp.int32),
                         q_patches.astype(jnp.float32), q_mask,
                         q_sal.astype(jnp.float32), rel)


def make_topic_banks(key: Array, spec: CorpusSpec) -> Array:
    """Patch prototypes (n_topics, patches_per_topic, D), drawn as in
    `make_retrieval_corpus`: a topic centre plus per-prototype spread."""
    k_c, k_p = jax.random.split(key)
    centers = jax.random.normal(k_c, (spec.n_topics, spec.dim))
    return centers[:, None, :] + 0.7 * jax.random.normal(
        k_p, (spec.n_topics, spec.patches_per_topic, spec.dim))


@partial(jax.jit, static_argnames=("spec", "n"))
def make_page_chunk(key: Array, banks: Array, spec: CorpusSpec, n: int
                    ) -> Tuple[Array, Array, Array]:
    """n pages for corpora built chunk by chunk (no (Q, N) relevance).

    A page holds its own `page_protos` salient and `page_protos`
    background prototypes, each drawn from the whole bank, so its set of
    patch clusters (and of codes, once quantized) is its own: pages do
    not share one ADC score by the thousand, as they would if a page's
    clusters were fixed by its topic. Generated on the device in one
    program; returns (patches (n, Md, D) f32 unit-norm, mask (n, Md)
    bool, salience (n, Md) f32).
    """
    md, d = spec.n_patches, spec.dim
    t, p, s = spec.n_topics, spec.patches_per_topic, spec.page_protos
    n_sal = max(1, int(md * spec.salient_frac))
    ks = jax.random.split(key, 7)
    own_sal = jax.random.randint(ks[0], (n, s), 0, t * p)
    own_bg = jax.random.randint(ks[1], (n, s), 0, t * p)
    proto = jnp.concatenate([
        jnp.take_along_axis(
            own_sal, jax.random.randint(ks[2], (n, n_sal), 0, s), axis=1),
        jnp.take_along_axis(
            own_bg, jax.random.randint(ks[3], (n, md - n_sal), 0, s),
            axis=1)], axis=1)                              # (n, Md)
    patches = banks.reshape(t * p, d)[proto]
    patches = patches + spec.noise * jax.random.normal(ks[4], patches.shape)
    patches = patches / jnp.linalg.norm(patches, axis=-1, keepdims=True)
    sal = jnp.concatenate([
        0.8 + 0.2 * jax.random.uniform(ks[5], (n, n_sal)),
        0.2 * jax.random.uniform(ks[6], (n, md - n_sal))], axis=1)
    return patches, jnp.ones((n, md), bool), sal


@partial(jax.jit, static_argnames=("spec",))
def make_page_queries(key: Array, patches: Array, targets: Array,
                      spec: CorpusSpec) -> Tuple[Array, Array, Array]:
    """Queries built from the salient patches of `targets` pages (rows of
    `patches`) plus noise, as in `make_retrieval_corpus`. Returns
    (embeddings (Q, Mq, D), mask (Q, Mq) bool, salience (Q, Mq))."""
    n_sal = max(1, int(spec.n_patches * spec.salient_frac))
    q, mq = targets.shape[0], spec.n_q_patches
    k_pick, k_noise, k_sal = jax.random.split(key, 3)
    pick = jax.random.randint(k_pick, (q, mq), 0, n_sal)
    emb = patches[targets[:, None], pick]
    emb = emb + spec.noise * jax.random.normal(k_noise, emb.shape)
    emb = emb / jnp.linalg.norm(emb, axis=-1, keepdims=True)
    sal = 0.5 + 0.5 * jax.random.uniform(k_sal, (q, mq))
    return emb, jnp.ones((q, mq), bool), sal


# ---------------------------------------------------------------------------
# RAG fact corpus (paper Table V)
# ---------------------------------------------------------------------------

class FactCorpus(NamedTuple):
    doc_patches: Array     # (N, Md, D)
    doc_mask: Array
    doc_salience: Array
    doc_facts: Array       # (N, F) int32 fact ids carried by each doc
    doc_tokens: Array      # (N, Ld) int32 generator-side rendering
    query_tokens: Array    # (Q, Lq) int32
    query_patches: Array   # (Q, Mq, D) retriever-side rendering
    query_mask: Array
    query_salience: Array
    gold_doc: Array        # (Q,) the doc answering each query
    gold_facts: Array      # (Q, F) reference facts (= gold doc's facts)


def make_fact_corpus(key: Array, n_docs: int = 256, n_facts_vocab: int = 200,
                     facts_per_doc: int = 4, dim: int = 64,
                     n_patches: int = 16, n_queries: int = 64,
                     seq_len: int = 32) -> Tuple[FactCorpus, Dict[str, int]]:
    """Legal-summarisation stand-in where hallucination is measurable.

    Token layout: [0] PAD, [1] SEP, [2] QUERY-marker,
    [3 .. 3+n_facts_vocab) fact tokens. A doc's tokens are its fact tokens;
    a query asks (via the QUERY marker + one probe fact token) for the doc
    containing that fact; the reference summary is the gold doc's fact set.
    """
    vocab = {"pad": 0, "sep": 1, "query": 2, "fact0": 3,
             "size": 3 + n_facts_vocab}
    ks = iter(jax.random.split(key, 10))

    # each fact id has a patch-space prototype: retrieval is fact matching
    fact_proto = jax.random.normal(next(ks), (n_facts_vocab, dim))
    doc_facts = jax.random.randint(next(ks), (n_docs, facts_per_doc),
                                   0, n_facts_vocab)
    # patches: facts repeated + noise
    reps = n_patches // facts_per_doc
    pat_f = jnp.repeat(doc_facts, reps, axis=1)[:, :n_patches]
    patches = fact_proto[pat_f] + 0.15 * jax.random.normal(
        next(ks), (n_docs, n_patches, dim))
    patches = patches / jnp.linalg.norm(patches, axis=-1, keepdims=True)
    sal = jnp.ones((n_docs, n_patches), jnp.float32)
    mask = jnp.ones((n_docs, n_patches), bool)

    # generator-side doc tokens: fact tokens separated by SEP, padded
    dt = jnp.full((n_docs, seq_len), vocab["pad"], jnp.int32)
    dt = dt.at[:, :facts_per_doc].set(doc_facts + vocab["fact0"])
    dt = dt.at[:, facts_per_doc].set(vocab["sep"])

    # queries: probe one fact of a gold doc
    gold_doc = jax.random.randint(next(ks), (n_queries,), 0, n_docs)
    probe_slot = jax.random.randint(next(ks), (n_queries,), 0, facts_per_doc)
    probe_fact = doc_facts[gold_doc, probe_slot]               # (Q,)
    qt = jnp.full((n_queries, 4), vocab["pad"], jnp.int32)
    qt = qt.at[:, 0].set(vocab["query"])
    qt = qt.at[:, 1].set(probe_fact + vocab["fact0"])
    qt = qt.at[:, 2].set(vocab["sep"])

    mq = 4
    q_patches = jnp.stack([fact_proto[probe_fact]] * mq, axis=1)
    q_patches = q_patches + 0.15 * jax.random.normal(next(ks), q_patches.shape)
    q_patches = q_patches / jnp.linalg.norm(q_patches, axis=-1, keepdims=True)

    fc = FactCorpus(
        patches.astype(jnp.float32), mask, sal, doc_facts.astype(jnp.int32),
        dt, qt, q_patches.astype(jnp.float32),
        jnp.ones((n_queries, mq), bool), jnp.ones((n_queries, mq)),
        gold_doc.astype(jnp.int32),
        doc_facts[gold_doc].astype(jnp.int32))
    return fc, vocab


# ---------------------------------------------------------------------------
# LM token streams (order-2 Markov chain — learnable)
# ---------------------------------------------------------------------------

def make_lm_batch(key: Array, vocab: int, batch: int, seq: int,
                  n_states: int = 64) -> Dict[str, Array]:
    k1, k2, k3 = jax.random.split(key, 3)
    # sparse-ish transition structure over a reduced state space
    trans = jax.random.dirichlet(k1, jnp.ones((4,)) * 0.5,
                                 (n_states, n_states))          # top-4 moves
    nxt = jax.random.randint(k2, (n_states, n_states, 4), 0, n_states)

    def gen(key):
        def step(carry, k):
            s1, s2 = carry
            p = trans[s1, s2]
            choice = jax.random.categorical(k, jnp.log(p + 1e-9))
            s3 = nxt[s1, s2, choice]
            return (s2, s3), s3
        ks = jax.random.split(key, seq + 1)
        init = (jnp.int32(0), jnp.int32(1))
        _, toks = jax.lax.scan(step, init, ks)
        return toks

    toks = jax.vmap(gen)(jax.random.split(k3, batch)) % vocab
    return {"tokens": toks[:, :seq].astype(jnp.int32),
            "targets": toks[:, 1:seq + 1].astype(jnp.int32)}


# ---------------------------------------------------------------------------
# Graphs
# ---------------------------------------------------------------------------

def make_graph(key: Array, n_nodes: int, n_edges: int, d_feat: int,
               n_classes: int, n_comm: int = 8) -> Dict[str, Array]:
    """Community-structured graph: labels correlate with communities and
    features correlate with labels (so PNA can learn)."""
    k1, k2, k3, k4, k5, k6, k7 = jax.random.split(key, 7)
    comm = jax.random.randint(k1, (n_nodes,), 0, n_comm)
    # intra-community edges (80%) + random (20%)
    n_intra = int(n_edges * 0.8)
    src_i = jax.random.randint(k2, (n_intra,), 0, n_nodes)
    # destination within same community: resample via sorting trick
    perm = jnp.argsort(comm)
    pos_of = jnp.argsort(perm)
    # neighbour in sorted order (same community w.h.p.)
    off = jax.random.randint(k3, (n_intra,), 1, 5)
    dst_i = perm[jnp.clip(pos_of[src_i] + off, 0, n_nodes - 1)]
    src_r = jax.random.randint(k4, (n_edges - n_intra,), 0, n_nodes)
    dst_r = jax.random.randint(k5, (n_edges - n_intra,), 0, n_nodes)
    src = jnp.concatenate([src_i, src_r])
    dst = jnp.concatenate([dst_i, dst_r])
    labels = comm % n_classes
    centers = jax.random.normal(k6, (n_classes, d_feat))
    feats = centers[labels] + 0.8 * jax.random.normal(k7, (n_nodes, d_feat))
    return {"feats": feats.astype(jnp.float32),
            "edge_index": jnp.stack([src, dst]).astype(jnp.int32),
            "labels": labels.astype(jnp.int32)}


def make_molecule_batch(key: Array, n_graphs: int, nodes_per: int,
                        edges_per: int, d_feat: int) -> Dict[str, Array]:
    k1, k2, k3 = jax.random.split(key, 3)
    n = n_graphs * nodes_per
    feats = jax.random.normal(k1, (n, d_feat))
    # ring + random chords per graph, offset per graph id
    base = jnp.arange(nodes_per)
    ring_src = jnp.tile(base, n_graphs)
    ring_dst = jnp.tile((base + 1) % nodes_per, n_graphs)
    off = jnp.repeat(jnp.arange(n_graphs) * nodes_per, nodes_per)
    extra = edges_per - nodes_per
    es = jax.random.randint(k2, (n_graphs, extra), 0, nodes_per)
    ed = jax.random.randint(k3, (n_graphs, extra), 0, nodes_per)
    goff = jnp.arange(n_graphs)[:, None] * nodes_per
    src = jnp.concatenate([ring_src + off, (es + goff).ravel()])
    dst = jnp.concatenate([ring_dst + off, (ed + goff).ravel()])
    graph_ids = jnp.repeat(jnp.arange(n_graphs), nodes_per)
    # label: does mean feature exceed 0 in dim 0 (learnable)
    pooled = jax.ops.segment_sum(feats[:, 0], graph_ids, num_segments=n_graphs)
    labels = (pooled > 0).astype(jnp.int32)
    return {"feats": feats.astype(jnp.float32),
            "edge_index": jnp.stack([src, dst]).astype(jnp.int32),
            "graph_ids": graph_ids.astype(jnp.int32),
            "graph_labels": labels}


# ---------------------------------------------------------------------------
# RecSys batches (planted logistic teacher)
# ---------------------------------------------------------------------------

def make_recsys_batch(key: Array, batch: int, n_dense: int,
                      table_rows, seq_len: int = 0,
                      family: str = "dlrm") -> Dict[str, Array]:
    ks = iter(jax.random.split(key, 8))
    if family in ("din", "dien"):
        n_items = table_rows[0]
        hist = jax.random.randint(next(ks), (batch, seq_len), 0, n_items)
        hl = jax.random.randint(next(ks), (batch,), seq_len // 2, seq_len + 1)
        mask = jnp.arange(seq_len)[None, :] < hl[:, None]
        target = jax.random.randint(next(ks), (batch,), 0, n_items)
        # planted signal: click if target shares low bits with history mode
        sig = (jnp.sum((hist % 7) * mask, axis=1) % 7) == (target % 7)
        noise = jax.random.bernoulli(next(ks), 0.1, (batch,))
        label = jnp.logical_xor(sig, noise).astype(jnp.float32)
        return {"hist_ids": hist.astype(jnp.int32), "hist_mask": mask,
                "target_ids": target.astype(jnp.int32), "label": label}
    dense = jax.random.normal(next(ks), (batch, n_dense))
    sparse = jnp.stack([jax.random.randint(next(ks), (batch,), 0, r)
                        for r in table_rows], axis=1)
    w = jax.random.normal(next(ks), (n_dense,))
    logit = dense @ w + 0.5 * jnp.sum((sparse % 5) - 2, axis=1) / len(table_rows)
    label = (jax.nn.sigmoid(logit)
             > jax.random.uniform(next(ks), (batch,))).astype(jnp.float32)
    return {"dense": dense.astype(jnp.float32),
            "sparse_ids": sparse.astype(jnp.int32), "label": label}
