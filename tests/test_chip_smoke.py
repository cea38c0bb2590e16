"""chip_smoke.py's reference check and the page build it drives, on the CPU.

The smoke itself needs a TPU. How it compares served results with the
reference, and the chunked build and CLI it shares with
`repro.launch.serve`, are plain code and are checked here.
"""
import dataclasses
import importlib.util
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.data import synthetic
from repro.launch import serve
from repro.retrieval import HPCConfig, Retriever

ROOT = os.path.join(os.path.dirname(__file__), "..")
_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)

K = 128


def _reference(rows=4, seed=0):
    """Top-k rows with distinct descending scores, except one tie group of
    three at ranks 10-12 of every row."""
    rng = np.random.default_rng(seed)
    scores = np.sort(rng.uniform(1.0, 30.0, (rows, K)), axis=1)[:, ::-1]
    scores[:, 11:13] = scores[:, 10:11]
    ids = np.stack([rng.permutation(1 << 20)[:K] for _ in range(rows)])
    return scores.astype(np.float32), ids.astype(np.int32)


def _tie_permuted(s, i):
    i = i.copy()
    i[:, 10:13] = i[:, 10:13][:, ::-1]
    return s, i


def _shifted(s, i):
    return s, np.roll(i, 1, axis=1)


def _lane_swap(s, i):
    i = i.copy()
    i[:, [40, 41]] = i[:, [41, 40]]
    return s, i


def _score_off(s, i):
    s = s.copy()
    s[0, 5] += 1e-3
    return s, i


def _all_tied(s, i):
    return np.full_like(s, 7.0), i


@pytest.mark.parametrize("case,ok", [
    (lambda s, i: (s, i), True),
    (_tie_permuted, True),
    (_shifted, False),
    (_lane_swap, False),
    (_score_off, False),
    (_all_tied, False),
], ids=["identical", "tie_permuted", "shifted", "lane_swap", "score_off",
        "all_tied"])
def test_compare_verdicts(case, ok):
    """Ids are checked as sets within tie groups; a row that is one tie
    group checks no id, so it fails rather than passes."""
    ref_s, ref_i = _reference()
    s, i = case(ref_s, ref_i)
    if case is _all_tied:
        ref_s = s
    assert smoke.compare(s, i, ref_s, ref_i)[0] is ok


def test_check_against_plants_a_wrong_answer():
    ref_s, ref_i = _reference()
    assert "control" in smoke.check_against(ref_s, ref_i, ref_s, ref_i, "x")
    # ids that do not depend on the rank: the shifted control passes too,
    # so the check could not see a wrong id and must say so
    same = np.zeros_like(ref_i)
    with pytest.raises(smoke.SmokeFailure, match="shifted"):
        smoke.check_against(ref_s, same, ref_s, same, "x")


@pytest.mark.parametrize("n_pages", [40, 100])
def test_build_pages_indexes_what_was_asked(monkeypatch, n_pages):
    """First chunk clamped to the page count; a ragged last chunk is
    added and folded by compact."""
    monkeypatch.setattr(serve, "PAGE_CHUNK", 48)
    spec = dataclasses.replace(synthetic.VIDORE, n_patches=16,
                               n_q_patches=4, dim=16)
    r = Retriever(HPCConfig(k=16, p=60.0, prune_side="doc", backend="flat",
                            rerank=8, kmeans_iters=3, kmeans_restarts=1))
    state, queries, targets, pages, reason = serve.build_pages(
        r, jax.random.PRNGKey(0), spec, n_pages, 8)
    assert pages == n_pages and reason == "target reached"
    # one chunk is a plain build; more are segments folded by compact
    live = r.build_stats(state).get("live_docs", state.rerank_codes.shape[0])
    assert live == n_pages
    assert queries.embeddings.shape == (8, 4, 16)
    assert np.all(targets < min(48, n_pages))
    _, ids = r.search(state, queries, k=4)
    assert np.mean([t in row for t, row in zip(targets, np.asarray(ids))]) \
        >= 0.5


def test_page_chunk_gives_pages_their_own_code_sets():
    """Two pages share a code set (and so every ADC score) only by chance."""
    spec = dataclasses.replace(synthetic.VIDORE, n_patches=64, dim=32)
    banks = synthetic.make_topic_banks(jax.random.PRNGKey(0), spec)
    patches, _, _ = synthetic.make_page_chunk(jax.random.PRNGKey(1), banks,
                                              spec, 512)
    flat = np.asarray(banks).reshape(-1, spec.dim)
    flat = flat / np.linalg.norm(flat, axis=1, keepdims=True)
    nearest = np.argmax(np.asarray(patches) @ flat.T, axis=-1)   # (n, Md)
    sets = {frozenset(row.tolist()) for row in nearest}
    assert len(sets) == 512


def test_serve_cli_at_colpali_widths_indexes_the_pages_asked_for(tmp_path):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "colpali-hpc",
         "--pages", "256", "--queries", "8"],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "indexed 256 pages" in out.stdout
    assert "hit@128 1.000" in out.stdout
