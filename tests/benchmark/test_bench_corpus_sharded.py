"""The four-chip deployment `colpali-hpc-4chip` without a chip: its system
(`systems/corpus_sharded.py`) through the whole harness on four virtual
CPU devices, and the readers of its per-layer metrics."""
from __future__ import annotations

import json
import os
from types import SimpleNamespace

import pytest

from benchmarks.chip.catalog import Catalog
from benchmarks.chip.trace import Trace

CATALOG = Catalog()
CELL = "colpali-hpc-4chip.shard-open"
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures")
READERS = ("device.allgather_pct.sharded", "device.chip_skew_pct.sharded",
           "adc_roofline.sharded")
# the one-chip cells' readers, read in this cell too
SHARED = ("search.ms_per_query.sharded", "device.busy_ms_per_query.sharded",
          "loadgen.late_p99_ms.sharded")


def test_cell_is_the_pdfa_corpus_cut_to_four_chips():
    cell = CATALOG.cell(CELL)
    config, workload = cell["config"], cell["workload"]
    assert cell["entry"]["chips"] == 4
    # the corpus is cut, and says so in both places
    assert workload["pages"] == 1 << 22 < config["published"]["corpus_docs"]
    assert list(config["reduced"]) == ["corpus_docs"] == next(
        c for c in CATALOG.benchmark["configs"]
        if c["name"] == "colpali-hpc-4chip")["reduced"]
    assert CATALOG.system(config).CHIPS == (4,)
    # every chip's share is whole chunks, each a whole number of the
    # reference's blocks
    assert workload["pages"] // 4 % workload["chunk_pages"] == 0
    assert workload["chunk_pages"] % 256 == 0
    one = CATALOG.config("colpali-hpc")
    for key in ("encoder", "hpc", "top_k", "max_batch", "pages", "assumed"):
        assert config[key] == one[key], key
    ref = CATALOG.module("references", config["reference"])
    assert ref.LIMITS == CATALOG.module("references", "adc_rerank").LIMITS
    assert {m["name"] for m in cell["end_to_end"]} == {
        "p50_ms", "p95_ms", "index_bytes_per_page", "setup_s"}
    assert {m["name"] for m in cell["per_layer"]} == set(READERS + SHARED)


def test_four_chip_system_through_the_harness(tmp_path):
    """A tiny cell served by `corpus_sharded` on four virtual devices:
    built chunk by chunk on the device that owns each chunk, its answers
    pass the reference spread over the devices, the result counts four
    chips, and answers rotated a request over read `correct` false."""
    from tests.conftest import run_subprocess

    here = os.path.dirname(os.path.abspath(__file__))
    out = run_subprocess(f"""
import json, os, sys
sys.path[:0] = [{os.path.dirname(os.path.dirname(here))!r}, {here!r}]
import jax
import jax.numpy as jnp
from bench_tiny import tiny_catalog
from benchmarks.chip import run_cell

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

root = {str(tmp_path)!r}
cat = tiny_catalog(root, chips=4)
path = os.path.join(root, "benchmarks", "chip")
with open(os.path.join(path, "configs", "tiny.json")) as f:
    config = json.load(f)
config.update(system="corpus_sharded", reference="adc_rerank_chips")
with open(os.path.join(path, "configs", "tiny.json"), "w") as f:
    json.dump(config, f)
# four shares of 256 pages, one chunk of the reference's blocks each
with open(os.path.join(path, "workloads", "tiny.closed.json"), "w") as f:
    json.dump({{"pages": 1024, "chunk_pages": 256, "queries": 64,
               "check_requests": 16, "warm_rungs": [8]}}, f)
chips = [AsTpu(d) for d in jax.devices()]
devices = [c.d for c in run_cell.cell_devices(cat, "tiny.closed", chips)]
runs = [run_cell.run(cat, "tiny.closed", 2**33 + 5, 1.0, False, devices,
                     wrap_search=fault) for fault in (None, rotated)]
print(json.dumps([{{k: r[k] for k in ("correct", "device", "checks",
                                     "metrics", "setup_phases_s")}}
                  for r in runs]))
""", n_devices=4)
    sound, rot = json.loads(out.strip().splitlines()[-1])
    assert sound["correct"], sound["checks"]
    assert sound["checks"]["score_gap"]["value"] <= 2e-6
    assert sound["device"]["count"] == 4
    assert len(sound["device"]["memory_peak_bytes_per_chip"]) == 4
    assert list(sound["setup_phases_s"])[:2] == ["fit", "generate+encode"]
    assert sound["metrics"]["index_bytes_per_page"]["value"] > 0
    assert not rot["correct"], rot["checks"]
    assert rot["checks"]["wrong_ids"]["value"] > 0


def test_readers_on_the_recorded_one_chip_trace():
    """On the recorded one-chip trace (one chip, no all-gather) the two
    new readers read nothing; `adc_roofline.sharded` shares the reader of
    `adc_roofline.open` and reads what it reads."""
    from benchmarks.chip.spantrace import ScopedTrace

    tr = ScopedTrace.from_json(os.path.join(FIXTURE,
                                            "v5e_scoped_search.json.gz"))
    run = SimpleNamespace(
        trace=tr, traces=[tr], catalog=CATALOG,
        config=CATALOG.json("configs", "colpali-hpc"), pages=1 << 18,
        peaks=CATALOG.json(".", "peaks")["devices"]["TPU v5 lite"],
        traced_spans=[(0.0, 0.1, 1, 1)])

    def read(name):
        return CATALOG.module("metrics", name).read(run)

    assert read("device.allgather_pct.sharded") is None
    assert read("device.chip_skew_pct.sharded") is None
    assert read("adc_roofline.sharded") == read("adc_roofline.open") > 0


def test_readers_on_four_chips():
    """Four chips' traces with a planted all-gather pair and unequal busy
    time: the all-gathers' share of the summed busy time, the spread of
    busy time over its mean, and the ADC kernel's roofline over its time
    summed over the chips."""
    from benchmarks.chip import readers

    ms = 10**6
    window = [("bench.window", 0, 100 * ms)]
    traces = []
    for kernel_ms in (40, 50, 50, 60):
        ops = [("quantized_maxsim_pallas_scan.1", 0, kernel_ms * ms),
               ("all-gather-start.2", kernel_ms * ms, (kernel_ms + 2) * ms),
               ("all-gather-done.3", (kernel_ms + 2) * ms,
                (kernel_ms + 3) * ms),
               ("while.4", (kernel_ms + 3) * ms, (kernel_ms + 10) * ms),
               ("fusion.5", (kernel_ms + 4) * ms, (kernel_ms + 5) * ms)]
        traces.append(Trace(ops=ops, spans=list(window)))
    config = CATALOG.json("configs", "colpali-hpc-4chip")
    peaks = CATALOG.json(".", "peaks")["devices"]["TPU v5 lite"]
    run = SimpleNamespace(trace=traces[0], traces=traces, catalog=CATALOG,
                          config=config, pages=1 << 22, peaks=peaks,
                          traced_spans=[(0.0, 1.0, 1, 1)])
    busy = [0.05, 0.06, 0.06, 0.07]
    assert readers.busy_s_per_chip(run) == pytest.approx(busy)

    def read(name):
        return CATALOG.module("metrics", name).read(run)

    # 3 ms of all-gather a chip in 240 ms of busy time
    assert read("device.allgather_pct.sharded") == pytest.approx(
        100 * 4 * 0.003 / sum(busy))
    assert read("device.chip_skew_pct.sharded") == pytest.approx(
        100 * (0.07 - 0.05) / 0.06)
    ops, nbytes = CATALOG.module("kernels", "adc").search_counts(
        config, 1 << 22, 1)
    least = max(ops / max(peaks["ops_per_s"].values()),
                nbytes / peaks["hbm_bytes_per_s"])
    assert read("adc_roofline.sharded") == pytest.approx(
        100 * least / 0.2)
    # 240 ms of busy time over four chips and two queries
    run.records, run.t1 = [{"result": 1, "done": 0.5},
                           {"result": 1, "done": 0.9}], 1.0
    assert read("device.busy_ms_per_query.sharded") == pytest.approx(
        1e3 * sum(busy) / 4 / 2)
    # no all-gather in the window: nothing to read
    for t in traces:
        t.ops = [o for o in t.ops if not o[0].startswith("all-gather")]
    assert read("device.allgather_pct.sharded") is None


def test_shared_readers_are_the_one_chip_cells():
    """The cell's host readers are the open cell's, found by suffix; its
    busy time per query is the chips' mean, and one chip reads as
    `device.busy_ms_per_query.open` reads."""
    for name in ("search.ms_per_query", "loadgen.late_p99_ms"):
        assert (CATALOG.module("metrics", f"{name}.sharded").read.__code__
                .co_code == CATALOG.module("metrics", name).read.__code__
                .co_code)
    ms = 10**6
    tr = Trace(ops=[("fusion.1", 0, 30 * ms)],
               spans=[("bench.window", 0, 100 * ms)])
    run = SimpleNamespace(trace=tr, traces=[tr], t1=1.0,
                          records=[{"result": 1, "done": 0.5},
                                   {"result": 1, "done": 2.0}])
    one = CATALOG.module("metrics", "device.busy_ms_per_query.open").read
    assert CATALOG.module("metrics", "device.busy_ms_per_query.sharded").read(
        run) == one(run) == pytest.approx(30.0)
    run.trace = None
    assert CATALOG.module("metrics", "device.busy_ms_per_query.sharded").read(
        run) is None
