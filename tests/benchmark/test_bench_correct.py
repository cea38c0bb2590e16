"""The comparison that decides a chip run's `correct`, driven through
the whole harness at a tiny size on the CPU (the chip check skipped):
sound runs pass, the lower-precision control and planted faults of the
served path fail."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from bench_tiny import tiny_catalog
from benchmarks.chip import check, run_cell

SEED = 2**33 + 5       # past 32 bits: seeds may exceed an int32


@pytest.mark.parametrize("backend", ["flat", "hamming"])
def test_control_is_not_correct(tmp_path, backend):
    cat = tiny_catalog(tmp_path, backend)
    cell = run_cell.Cell(cat, "tiny.closed", SEED, annotate=False)
    run = cell.window(cell.mix, 1.0, SEED, False, jax.devices()[0])
    ref_mod = cat.module("references", cell.config["reference"])
    answers, refs, rows, unanswered, excess = cell.references(
        run, SEED, ref_mod.VARIANTS)
    assert unanswered == 0 and answers

    def judge(ans):
        return check.compare(ans, refs["reference"], rows,
                             limits=cell.limits(), unanswered=0,
                             codebook_excess=excess["program"])

    numbers, ok = judge(answers)
    assert ok, numbers
    # the reference itself, put in the program's place, passes
    assert judge([check.reference_answers(refs["reference"], 16)[r]
                  for r in rows])[1]
    control = {"flat": "bf16", "hamming": "bits-1"}[backend]
    placed = check.reference_answers(refs[control], 16)
    numbers, ok = judge([placed[r] for r in rows])
    assert not ok, numbers


def _altered(fn):
    def served(q, qm, qs):
        scores, ids = fn(q, qm, qs)
        return scores, ids.at[:, 0].set((ids[:, 0] + 1) % 512)
    return served


def _rotated(fn):
    def served(q, qm, qs):
        scores, ids = fn(q, qm, qs)
        return jnp.roll(scores, 1, axis=0), jnp.roll(ids, 1, axis=0)
    return served


def _half_left_out(fn):
    def served(q, qm, qs):
        scores, ids = fn(q, qm, qs)
        h = -(-q.shape[0] // 2)
        rest = q.shape[0] - h
        return (jnp.concatenate([scores[:h], scores[:rest]]),
                jnp.concatenate([ids[:h], ids[:rest]]))
    return served


@pytest.mark.parametrize("fault", [None, _altered, _rotated,
                                   _half_left_out])
def test_fault_in_served_path_is_not_correct(tmp_path, fault):
    cat = tiny_catalog(tmp_path)
    result = run_cell.run(cat, "tiny.closed", SEED, 1.0, False,
                          jax.devices()[0], wrap_search=fault)
    assert result["correct"] is (fault is None), result["checks"]
    assert list(result)[-1] == "checks"
    if fault is None:
        assert set(result["metrics"]) == {"qps", "index_bytes_per_page",
                                          "setup_s"}


def test_open_loop_run_reports_latency(tmp_path):
    cat = tiny_catalog(tmp_path)
    result = run_cell.run(cat, "tiny.open", SEED, 2.0, False,
                          jax.devices()[0])
    assert result["correct"], result["checks"]
    m = result["metrics"]
    assert set(m) == {"p50_ms", "p95_ms", "index_bytes_per_page",
                      "setup_s"}
    assert 0 < m["p50_ms"]["value"] <= m["p95_ms"]["value"]
    assert result["attempted"] == 40 and result["failed"] == 0


def test_weak_codebook_is_not_correct(tmp_path, monkeypatch):
    """A codebook fit that merges clusters: the served scores still match
    the reference, which scores with the program's codebook, and the
    codebook's own check reads `correct` false."""
    from repro.core import quantization

    def first_rows(key, x, config):
        return x[:config.k], jnp.zeros((config.iters,), x.dtype)

    monkeypatch.setattr(quantization, "kmeans_fit", first_rows)
    cat = tiny_catalog(tmp_path)
    result = run_cell.run(cat, "tiny.closed", SEED, 1.0, False,
                          jax.devices()[0])
    checks = result["checks"]
    assert not result["correct"]
    assert checks["wrong_ids"]["value"] == 0
    assert checks["codebook_excess"]["value"] > checks[
        "codebook_excess"]["limit"]
