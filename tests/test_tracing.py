"""The tracer (`repro.tracing`) and the spans the server records on it."""
from __future__ import annotations

import asyncio
import threading

import numpy as np
import pytest

from repro.serving.server import AsyncRetrievalServer, ServeConfig
from repro import tracing
from repro.tracing import Tracer

Q = (np.zeros((4, 16), np.float32), np.ones(4, bool),
     np.zeros(4, np.float32))


def _search(q, qm, qs):
    b = q.shape[0]
    return (np.tile(np.arange(5, dtype=np.float32), (b, 1)),
            np.tile(np.arange(5, dtype=np.int32), (b, 1)))


def test_scoped_spans_nest_and_carry_parent_ids():
    tr = Tracer()
    with tr.span("outer", batch=7) as outer:
        with tr.span("inner") as inner:
            pass
        with tr.span("sibling", parent=123) as sib:
            pass
    recs = {r.name: r for r in tr.records()}
    assert recs["inner"].parent == outer and recs["inner"].id == inner
    assert recs["sibling"].parent == 123 and recs["sibling"].id == sib
    assert recs["outer"].parent is None
    assert recs["outer"].ids == {"batch": 7}
    o, i = recs["outer"], recs["inner"]
    assert o.start_ns <= i.start_ns <= i.end_ns <= o.end_ns
    # children are appended as they end, before their parent
    assert [r.name for r in tr.records()] == ["inner", "sibling", "outer"]


def test_parents_are_per_thread():
    tr = Tracer()
    seen = {}

    def worker():
        with tr.span("other-thread") as sid:
            seen["id"] = sid

    with tr.span("main"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    rec = tr.records("other-thread")[0]
    assert rec.parent is None and rec.id == seen["id"]


def test_mark_takes_a_reserved_id_and_stamps():
    tr = Tracer()
    rid = tr.new_id()
    got = tr.mark("serve.queue", 10, 25, parent=rid, request=rid)
    assert got != rid
    assert tr.mark("serve.request", 10, 40, span_id=rid, request=rid) == rid
    q, r = tr.records()
    assert (q.start_ns, q.end_ns, q.parent, q.ids) == (10, 25, rid,
                                                       {"request": rid})
    assert r.id == rid and r.ms == pytest.approx(30e-6)
    assert tr.records("serve.queue") == [q]
    assert tr.records(since_ns=30) == [r]


def test_ring_is_bounded(monkeypatch):
    monkeypatch.setattr(tracing, "CAPACITY", 8)
    tr = Tracer()
    for i in range(20):
        tr.mark("s", i, i + 1)
    recs = tr.records()
    assert len(recs) == 8
    assert [r.start_ns for r in recs] == list(range(12, 20))


def test_counters_count_and_drop_by_name():
    tr = Tracer()
    tr.count("serve.timeouts")
    tr.count("serve.deadline_expired", 3)
    tr.count("serve.deadline_expired", 2)
    tr.count("other", 1)
    assert tr.counters == {"serve.timeouts": 1, "serve.deadline_expired": 5,
                           "other": 1}
    tr.drop_counters("serve.timeouts", "serve.deadline_expired", "unseen")
    assert tr.counters == {"other": 1}


@pytest.mark.parametrize("annotate", [False, True])
def test_annotations_only_when_asked(monkeypatch, annotate):
    import jax

    made = []

    class Note:
        def __init__(self, name, **kw):
            made.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Note)
    tr = Tracer(annotate=annotate)
    with tr.span("serve.stage", batch=3, rung=4):
        pass
    tr.mark("serve.queue", 0, 1)          # a mark is never an annotation
    assert made == ([("serve.stage", {"batch": 3, "rung": 4})]
                    if annotate else [])


def _serve(n: int, cfg: ServeConfig, tracer=None):
    async def main():
        srv = AsyncRetrievalServer(_search, cfg, tracer=tracer)
        await srv.start()
        out = await asyncio.gather(*[srv.query(*Q) for _ in range(n)])
        stats = srv.stats()
        await srv.aclose()
        return srv, out, stats

    return asyncio.run(main())


def test_server_records_a_request_and_batch_tree():
    srv, out, stats = _serve(11, ServeConfig(max_batch=4, max_wait_ms=5.0))
    assert len(out) == 11
    recs = srv.tracer.records()
    by = {}
    for r in recs:
        by.setdefault(r.name, []).append(r)
    requests, batches = by["serve.request"], by["serve.batch"]
    assert len(requests) == 11
    # the batches carry their requests' ids, each request in one batch
    carried = [rid for b in batches for rid in b.ids["requests"]]
    assert sorted(carried) == sorted(r.id for r in requests)
    for b in batches:
        assert b.id == b.ids["batch"]
        assert b.ids["rung"] in srv.ladder
        assert len(b.ids["requests"]) <= b.ids["rung"]
        kids = [r for r in recs if r.parent == b.id]
        assert sorted(r.name for r in kids) == sorted(
            ("serve.coalesce", "serve.slot", "serve.stage",
             "serve.compute", "serve.d2h", "serve.fanout"))
        for k in kids:
            assert b.start_ns <= k.start_ns <= k.end_ns <= b.end_ns
    for r in requests:
        queue = [q for q in by["serve.queue"] if q.parent == r.id]
        assert len(queue) == 1 and queue[0].ids == {"request": r.id}
        assert r.start_ns == queue[0].start_ns <= queue[0].end_ns <= r.end_ns
        assert r.ids["batch"] in {b.id for b in batches}
    assert sum(v["batches"] * v["occupancy"] * r
               for r, v in stats["rungs"].items()) == pytest.approx(11)


def test_stats_read_the_records_as_the_lists_did():
    srv, _, stats = _serve(13, ServeConfig(max_batch=4, max_wait_ms=5.0))
    lat = srv.latencies_ms
    sizes = srv.batch_sizes
    assert len(lat) == stats["n"] == 13 and sum(sizes) == 13
    assert stats["p50_ms"] == pytest.approx(float(np.percentile(lat, 50)))
    assert stats["p99_ms"] == pytest.approx(float(np.percentile(lat, 99)))
    assert stats["mean_batch"] == pytest.approx(float(np.mean(sizes)))
    rungs = {}
    for b in srv.tracer.records("serve.batch"):
        c = rungs.setdefault(b.ids["rung"], [0, 0])
        c[0] += 1
        c[1] += len(b.ids["requests"])
    assert stats["rungs"] == {r: {"batches": n, "occupancy": rows / (n * r)}
                              for r, (n, rows) in sorted(rungs.items())}
    reqs = srv.tracer.records("serve.request")
    window = (max(r.end_ns for r in reqs) - min(r.start_ns for r in reqs))
    assert stats["qps"] == pytest.approx(13 / (window / 1e9))


def test_reset_stats_keeps_the_ring_and_restarts_the_window():
    tracer = Tracer()
    srv, _, _ = _serve(3, ServeConfig(max_batch=4, max_wait_ms=1.0),
                       tracer)
    n_records = len(tracer.records())
    tracer.count("serve.timeouts")
    tracer.count("serve.watchdog_restarts")
    assert srv.stats()["timeouts"] == 1
    srv.reset_stats()
    assert srv.stats()["n"] == 0 and srv.stats()["rungs"] == {}
    assert srv.stats()["timeouts"] == 0
    assert srv.latencies_ms == [] and srv.batch_sizes == []
    assert len(tracer.records()) == n_records
    # lifetime health survives a new window
    assert tracer.counters == {"serve.watchdog_restarts": 1}


def test_threads_share_a_tracer_without_losing_records():
    """More threads than cores recording spans and counting at once,
    with a short switch interval: no record, id or count is lost."""
    import os
    import sys

    tr = Tracer()
    n_threads, per = 2 * (os.cpu_count() or 2) + 2, 300
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per):
                with tr.span("outer"):
                    with tr.span("inner"):
                        tr.count("n")
                tr.count("m", 2)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    recs = tr.records()
    assert len(recs) == 2 * n_threads * per
    assert len({r.id for r in recs}) == len(recs)
    outer = {r.id for r in recs if r.name == "outer"}
    assert all(r.parent in outer for r in recs if r.name == "inner")
    assert tr.counters == {"n": n_threads * per, "m": 2 * n_threads * per}


@pytest.mark.parametrize("backend", ["flat", "hamming"])
def test_onehot_shared_queries_counts_queries_scored_in_groups(
        monkeypatch, backend):
    """`serve.onehot_shared_queries` counts the real queries of every
    batch whose rung the traced ADC scan scores in query groups (rungs
    above 1); a Hamming index runs no ADC kernel and counts none."""
    import jax

    from repro.data import synthetic
    from repro.kernels import quantized_maxsim as qk
    from repro.retrieval import Corpus, HPCConfig, Query, Retriever

    monkeypatch.setattr(qk, "_TRACED_GROUPS", {})
    key = jax.random.PRNGKey(0)
    spec = synthetic.CorpusSpec(n_docs=64, n_queries=8, n_patches=8,
                                n_q_patches=4, dim=16, n_topics=4)
    data = synthetic.make_retrieval_corpus(key, spec)
    r = Retriever(HPCConfig(k=16, backend=backend, scan_impl="interpret",
                            kmeans_iters=3, kmeans_restarts=1))
    state = r.build(key, Corpus(data.doc_patches, data.doc_mask,
                                data.doc_salience))
    search = jax.jit(lambda q, qm, qs: r.search(state, Query(q, qm, qs),
                                                k=5))
    queries = [tuple(np.asarray(a[i]) for a in (
        data.query_patches, data.query_mask, data.query_salience))
        for i in range(8)]

    async def main():
        srv = AsyncRetrievalServer(search, ServeConfig(max_batch=4,
                                                       max_wait_ms=20.0))
        srv.warm_shapes(*queries[0])
        await srv.start()
        await srv.query(*queries[0])                  # a batch of one
        await asyncio.gather(*[srv.query(*q) for q in queries[1:]])
        await srv.aclose()
        return srv

    srv = asyncio.run(main())
    grouped = sum(len(b.ids["requests"])
                  for b in srv.tracer.records("serve.batch")
                  if b.ids["rung"] > 1)
    counted = srv.tracer.counters.get("serve.onehot_shared_queries", 0)
    assert grouped >= 2
    assert counted == (grouped if backend == "flat" else 0)
