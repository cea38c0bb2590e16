"""RAG integration (paper §V-C / Table V): HPC-ColPali as the retriever for
a summarisation LM, with *exactly measurable* hallucination.

The synthetic legal corpus (data/synthetic.py::make_fact_corpus) gives every
document an explicit fact set. The pipeline:

  query -> HPC-ColPali retrieval (top-k docs) -> prompt
  [doc_1 facts .. doc_k facts, SEP, QUERY, probe, SEP] -> greedy decode
  of `facts_per_doc` answer tokens -> extracted fact ids.

Metrics (paper Table V definitions):
  hallucination rate — fraction of generated fact tokens NOT contained in
    the retrieved context (the model asserted something its sources don't
    support);
  ROUGE-L — LCS-based F1 between generated fact sequence and the gold
    summary (the gold document's fact set);
  end-to-end latency — retrieval + generation wall-clock.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pipeline as hpc
from repro.models import transformer as T
from repro.retrieval.base import Query as RQuery
from repro.retrieval.retriever import Retriever
from repro.tracing import Tracer

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class RAGConfig:
    retriever: hpc.HPCConfig = dataclasses.field(default_factory=hpc.HPCConfig)
    top_k_docs: int = 2
    facts_per_doc: int = 4
    fact0: int = 3               # first fact-token id (vocab layout)
    sep: int = 1
    max_answer: int = 4


def build_prompt(doc_tokens: Array, query_tokens: Array, cfg: RAGConfig,
                 prompt_len: int) -> Array:
    """Retrieved docs' tokens + query -> fixed-length prompt (B, prompt_len).

    doc_tokens: (B, k, Ld) the retrieved docs' token renderings.
    """
    b, k, ld = doc_tokens.shape
    # keep only the fact prefix of each doc (facts_per_doc + SEP)
    keep = cfg.facts_per_doc + 1
    ctx = doc_tokens[:, :, :keep].reshape(b, k * keep)
    q = query_tokens
    prompt = jnp.concatenate([ctx, q], axis=1)
    pad = prompt_len - prompt.shape[1]
    assert pad >= 0, (prompt.shape, prompt_len)
    return jnp.pad(prompt, ((0, 0), (0, pad)))


def greedy_generate(params, prompt: Array, cfg_lm: T.LMConfig,
                    max_new: int, prompt_len: int) -> Array:
    """Greedy decode max_new tokens after the prompt. Returns (B, max_new)."""
    b = prompt.shape[0]
    max_len = prompt_len + max_new
    logits, cache = T.prefill(params, prompt, cfg_lm, max_len=max_len)
    outs = []
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for i in range(max_new):
        outs.append(tok)
        if i == max_new - 1:
            break
        logits, cache = T.decode_step(params, tok, cache,
                                      jnp.int32(prompt_len + i), cfg_lm)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    return jnp.stack(outs, axis=1)


def extract_facts(tokens: np.ndarray, fact0: int, n_facts: int) -> List[set]:
    """Token rows -> sets of fact ids (non-fact tokens ignored)."""
    out = []
    for row in tokens:
        out.append({int(t) - fact0 for t in row
                    if fact0 <= int(t) < fact0 + n_facts})
    return out


def hallucination_rate(generated: Sequence[set],
                       context_facts: Sequence[set]) -> float:
    """Fraction of generated facts unsupported by the retrieved context."""
    total, bad = 0, 0
    for gen, ctx in zip(generated, context_facts):
        for f in gen:
            total += 1
            bad += f not in ctx
    return bad / max(total, 1)


def rouge_l(gen: Sequence[int], ref: Sequence[int]) -> float:
    """ROUGE-L F1 on token sequences."""
    g, r = list(gen), list(ref)
    if not g or not r:
        return 0.0
    dp = np.zeros((len(g) + 1, len(r) + 1), np.int32)
    for i in range(1, len(g) + 1):
        for j in range(1, len(r) + 1):
            dp[i, j] = (dp[i - 1, j - 1] + 1 if g[i - 1] == r[j - 1]
                        else max(dp[i - 1, j], dp[i, j - 1]))
    lcs = dp[-1, -1]
    prec, rec = lcs / len(g), lcs / len(r)
    return 0.0 if lcs == 0 else 2 * prec * rec / (prec + rec)


def rag_pipeline(index: "hpc.HPCIndex", gen_params, corpus, rag_cfg: RAGConfig,
                 lm_cfg: T.LMConfig, n_facts_vocab: int,
                 queries_slice: slice = slice(None)) -> Dict[str, float]:
    """Run retrieval + generation over the fact corpus; return Table V row."""
    q_emb = corpus.query_patches[queries_slice]
    q_mask = corpus.query_mask[queries_slice]
    q_sal = corpus.query_salience[queries_slice]
    q_tok = corpus.query_tokens[queries_slice]
    gold_facts = np.asarray(corpus.gold_facts[queries_slice])

    # each stage's span ends once its device work is done
    tracer = Tracer()
    retriever = Retriever(rag_cfg.retriever)
    with tracer.span("rag.retrieve"):
        _, ids = retriever.search(index, RQuery(q_emb, q_mask, q_sal),
                                  k=rag_cfg.top_k_docs)
        ids = jax.block_until_ready(jnp.maximum(ids, 0))

    doc_toks = corpus.doc_tokens[ids]                     # (B, k, Ld)
    keep = rag_cfg.facts_per_doc + 1
    prompt_len = rag_cfg.top_k_docs * keep + q_tok.shape[1]
    prompt = build_prompt(doc_toks, q_tok, rag_cfg, prompt_len)

    with tracer.span("rag.generate"):
        gen = greedy_generate(gen_params, prompt, lm_cfg, rag_cfg.max_answer,
                              prompt_len)
        gen = np.asarray(jax.block_until_ready(gen))
    t_retrieve, t_generate = (s.ms / 1e3 for s in tracer.records())

    ctx_facts_arr = np.asarray(corpus.doc_facts)[np.asarray(ids)]  # (B,k,F)
    ctx_sets = [set(row.ravel().tolist()) for row in ctx_facts_arr]
    gen_sets = extract_facts(gen, rag_cfg.fact0, n_facts_vocab)
    halluc = hallucination_rate(gen_sets, ctx_sets)

    rouges = [rouge_l(sorted(g), sorted(set(ref.tolist())))
              for g, ref in zip(gen_sets, gold_facts)]
    # answer accuracy: all gold facts generated
    correct = np.mean([set(ref.tolist()) <= g
                       for g, ref in zip(gen_sets, gold_facts)])
    b = gen.shape[0]
    return {
        "rouge_l": float(np.mean(rouges)),
        "hallucination": float(halluc),
        "answer_acc": float(correct),
        "latency_ms": (t_retrieve + t_generate) * 1e3 / b,
        "retrieve_ms": t_retrieve * 1e3 / b,
        "generate_ms": t_generate * 1e3 / b,
    }


def make_rag_train_batch(key: Array, corpus, vocab: Dict[str, int],
                         rag_cfg: RAGConfig, batch: int, seq_len: int,
                         n_docs: int) -> Dict[str, Array]:
    """Supervised RAG fine-tuning batch: prompt (gold doc + distractors in
    context) -> answer = gold doc's facts. Loss masked to answer positions."""
    k1, k2, k3 = jax.random.split(key, 3)
    gold = jax.random.randint(k1, (batch,), 0, n_docs)
    distract = jax.random.randint(k2, (batch, rag_cfg.top_k_docs - 1),
                                  0, n_docs)
    # randomise gold position within the context
    docs = jnp.concatenate([gold[:, None], distract], axis=1)
    perm = jax.vmap(lambda k: jax.random.permutation(k, rag_cfg.top_k_docs))(
        jax.random.split(k3, batch))
    docs = jnp.take_along_axis(docs, perm, axis=1)
    doc_toks = corpus.doc_tokens[docs]

    probe_slot = jax.random.randint(k3, (batch,), 0, rag_cfg.facts_per_doc)
    probe = corpus.doc_facts[gold, probe_slot] + vocab["fact0"]
    q_tok = jnp.zeros((batch, 4), jnp.int32)
    q_tok = q_tok.at[:, 0].set(vocab["query"])
    q_tok = q_tok.at[:, 1].set(probe)
    q_tok = q_tok.at[:, 2].set(vocab["sep"])

    keep = rag_cfg.facts_per_doc + 1
    prompt_len = rag_cfg.top_k_docs * keep + 4
    prompt = build_prompt(doc_toks, q_tok, rag_cfg, prompt_len)
    answer = corpus.doc_facts[gold] + vocab["fact0"]       # (B, F)
    full = jnp.concatenate([prompt, answer], axis=1)
    pad = seq_len + 1 - full.shape[1]
    assert pad >= 0
    full = jnp.pad(full, ((0, 0), (0, pad)))
    tokens = full[:, :-1]
    targets = full[:, 1:]
    # mask: only answer positions contribute
    pos = jnp.arange(seq_len)[None, :]
    is_answer = (pos >= prompt_len - 1) & (pos < prompt_len - 1
                                           + rag_cfg.facts_per_doc)
    targets = jnp.where(is_answer, targets, -1)
    return {"tokens": tokens, "targets": targets}
