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
    cell = run_cell.Cell(cat, "tiny.closed", SEED, annotate=False,
                         devices=jax.devices()[:1])
    run = cell.window(cell.mix, 1.0, SEED, False)
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
                          jax.devices()[:1], wrap_search=fault)
    assert result["correct"] is (fault is None), result["checks"]
    assert list(result)[-1] == "checks"
    if fault is None:
        assert set(result["metrics"]) == {"qps", "index_bytes_per_page",
                                          "setup_s"}


def test_open_loop_run_reports_latency(tmp_path):
    cat = tiny_catalog(tmp_path)
    result = run_cell.run(cat, "tiny.open", SEED, 2.0, False,
                          jax.devices()[:1])
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
                          jax.devices()[:1])
    checks = result["checks"]
    assert not result["correct"]
    assert checks["wrong_ids"]["value"] == 0
    assert checks["codebook_excess"]["value"] > checks[
        "codebook_excess"]["limit"]


def test_four_device_cell(tmp_path):
    """A tiny cell whose configuration names a system that keeps the
    index on a mesh of four (virtual CPU) devices, through the whole
    harness: the devices are picked as a chip run picks them, by the
    system's chip counts; its answers pass the unchanged reference, the
    result counts four chips and the index's bytes on every one of them,
    and answers rotated a request over read `correct` false."""
    import json
    import os

    from tests.conftest import run_subprocess

    here = os.path.dirname(os.path.abspath(__file__))
    out = run_subprocess(f"""
import json, sys
sys.path[:0] = [{os.path.dirname(os.path.dirname(here))!r}, {here!r}]
import jax
import jax.numpy as jnp
from bench_tiny import tiny_catalog
from benchmarks.chip import run_cell, system
from repro.retrieval import HPCConfig, Retriever

class AsTpu:
    # a CPU device as the device check sees a chip
    platform, device_kind = "tpu", "TPU v5 lite"
    def __init__(self, d):
        self.d, self.id = d, d.id

def rotated(fn):
    def served(q, qm, qs):
        scores, ids = fn(q, qm, qs)
        return jnp.roll(scores, 1, axis=0), jnp.roll(ids, 1, axis=0)
    return served

chips = [AsTpu(d) for d in jax.devices()]
try:
    run_cell.cell_devices(tiny_catalog({str(tmp_path / "one")!r}, chips=4),
                          "tiny.closed", chips)
    refused = None
except run_cell.RunRefused as e:
    refused = str(e)
cat = tiny_catalog({str(tmp_path / "four")!r}, system="sharded", chips=4)
devices = [c.d for c in run_cell.cell_devices(cat, "tiny.closed", chips)]
runs = [run_cell.run(cat, "tiny.closed", {SEED}, 1.0, False, devices,
                     wrap_search=fault) for fault in (None, rotated)]
config = cat.cell("tiny.closed")["config"]
state, _ = cat.system(config).build(
    Retriever(HPCConfig(**config["hpc"])), config, {SEED},
    cat.cell("tiny.closed")["workload"], devices, system.Phases())
leaves = jax.tree.leaves(state)
print(json.dumps({{
    "refused": refused, "devices": [d.id for d in devices],
    "runs": [{{"correct": r["correct"], "device": r["device"],
              "checks": r["checks"], "metrics": r["metrics"]}}
             for r in runs],
    "resident": system.resident_bytes(state),
    "shards": sum(s.data.nbytes for x in leaves
                  for s in x.addressable_shards),
    "logical": sum(x.nbytes for x in leaves),
    "replicated": sum(x.nbytes for x in leaves
                      if x.sharding.is_fully_replicated)}}))
""", n_devices=4)
    got = json.loads(out.strip().splitlines()[-1])
    assert "serves on (1,)" in got["refused"]
    assert got["devices"] == [0, 1, 2, 3]
    sound, rotated = got["runs"]
    assert sound["correct"], sound["checks"]
    assert sound["device"]["count"] == 4
    assert len(sound["device"]["memory_peak_bytes_per_chip"]) == 4
    # every chip's copy counts: a leaf replicated on four chips four
    # times, a leaf split over them once
    assert got["replicated"] > 0
    assert got["resident"] == got["shards"] == (got["logical"]
                                                + 3 * got["replicated"])
    assert sound["metrics"]["index_bytes_per_page"]["value"] == (
        got["resident"] / 512)
    assert not rotated["correct"], rotated["checks"]
    assert rotated["checks"]["wrong_ids"]["value"] > 0
