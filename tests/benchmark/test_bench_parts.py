"""Parts of the chip benchmark that need no chip: roofline counts, the
open-loop schedule, discovery of parts by name, and the device check."""
from __future__ import annotations

import asyncio
import json
import math
import os
import shutil
import time
from collections import Counter
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.chip import run_cell
from benchmarks.chip.catalog import HERE, Catalog

CATALOG = Catalog()
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures")


def test_adc_counts_by_hand():
    cfg = CATALOG.json("configs", "colpali-hpc")
    ops, nbytes = CATALOG.module("kernels", "adc").search_counts(
        cfg, pages=65536, real=3)
    # scan: 615 kept codes of 1024 (p=60); rerank: 128 candidates x 1024
    assert ops == 2 * 3 * 65536 * 32 * 615 + 2 * 3 * 128 * 32 * 1024
    table = 3 * 32 * 256 * 4
    assert nbytes == (65536 * 615 * 1 + table + 3 * 128 * 8
                      + 3 * 128 * 1024 * 1 + table + 3 * 128 * 8)


def test_hamming_counts_by_hand():
    cfg = CATALOG.json("configs", "colpali-hpc-binary")
    ops, nbytes = CATALOG.module("kernels", "hamming").search_counts(
        cfg, pages=262144, real=64)
    assert ops == 2 * 64 * 262144 * 32 * 615
    assert nbytes == 262144 * 615 * 9 / 8 + 64 * 32 * 9 / 8 + 64 * 128 * 8


def test_roofline_is_the_larger_bound_over_kernel_time():
    from benchmarks.chip import readers
    from benchmarks.chip.trace import Trace

    cfg = CATALOG.json("configs", "colpali-hpc")
    peaks = CATALOG.json(".", "peaks")["devices"]["TPU v5 lite"]
    tr = Trace(ops=[("quantized_maxsim_pallas.1", 0, 10**6),
                    ("copy.2", 10**6, 2 * 10**6)])
    run = SimpleNamespace(trace=tr, traces=[tr],
                          catalog=CATALOG, config=cfg, pages=65536,
                          peaks=peaks, traced_spans=[(0, 1, 1, 1)])
    ops, nbytes = CATALOG.module("kernels", "adc").search_counts(
        cfg, 65536, 1)
    least = max(ops / 393e12, nbytes / 819e9)
    assert readers.roofline_pct(run, "adc") == pytest.approx(
        100 * least / 1e-3)
    assert readers.roofline_pct(run, "hamming") is None  # nothing to read


def test_roofline_and_busy_time_over_every_chip():
    """On the recorded chip fixture, one trace reads what the one-chip
    harness read; four chips' traces of the same work read a quarter of
    the roofline (the cell's work over four chips' kernel time), and
    four equal busy times."""
    from benchmarks.chip import readers
    from benchmarks.chip.spantrace import ScopedTrace
    from benchmarks.chip.trace import top_ops

    tr = ScopedTrace.from_json(os.path.join(FIXTURE,
                                            "v5e_scoped_search.json.gz"))
    lo, hi = tr.window()

    def run(traces):
        return SimpleNamespace(
            trace=traces[0], traces=traces, catalog=CATALOG,
            config=CATALOG.json("configs", "colpali-hpc"), pages=1 << 18,
            peaks=CATALOG.json(".", "peaks")["devices"]["TPU v5 lite"],
            traced_spans=[(0.0, 0.1, 1, 1)])

    one, four = run([tr]), run([tr] * 4)
    assert readers.roofline_pct(one, "adc") == 0.17098157118412502
    assert readers.busy_s(one) == 0.124521741
    assert readers.busy_s_per_chip(one) == [0.124521741]
    assert readers.roofline_pct(four, "adc") == pytest.approx(
        0.17098157118412502 / 4, rel=1e-15)
    assert readers.busy_s(four) == 0.124521741
    assert readers.busy_s_per_chip(four) == [0.124521741] * 4
    assert top_ops([tr] * 4, lo, hi, 3) == [
        [name, pytest.approx(4 * s, rel=1e-15)]
        for name, s in top_ops([tr], lo, hi, 3)]
    assert top_ops([tr], lo, hi, 1) == [["quantized_maxsim_pallas_scan",
                                         0.115120826]]


def test_open_loop_schedule_is_a_poisson_sample_of_the_mix():
    drv = CATALOG.module("traffic", "open_loop")
    a, b = drv.schedule(5.3, 45.0, 1), drv.schedule(5.3, 45.0, 2)
    assert len(a) == len(b) == round(5.3 * 45)
    assert np.all(np.diff(a) > 0) and a[-1] < 45.0 and b[-1] < 45.0
    # another order seed gives the same gaps in another order
    assert np.allclose(np.sort(np.diff(a, prepend=0)),
                       np.sort(np.diff(b, prepend=0)))
    assert not np.allclose(a, b)
    gaps = np.diff(a, prepend=0)
    assert gaps.mean() == pytest.approx(45.0 / len(a), rel=0.02)
    # exponential gaps: their spread is their mean
    assert gaps.std() == pytest.approx(gaps.mean(), rel=0.1)


def test_open_loop_counts_lateness_from_due_time():
    drv = CATALOG.module("traffic", "open_loop")
    calls = []

    async def query(i):
        if not calls:
            time.sleep(0.3)   # a stall that blocks the loop
        calls.append(i)
        return i

    async def main():
        t0 = time.perf_counter()
        return t0, await drv.drive(query, 8, {"rate_qps": 20.0,
                                              "order_seed": 3}, t0, 1.0,
                                   7, give_up_s=5.0)

    t0, recs = asyncio.run(main())
    assert len(recs) == 20 and all("result" in r for r in recs)
    due = t0 + drv.schedule(20.0, 1.0, 3)
    assert np.allclose([r["due"] for r in recs], due)
    late = [r["sent"] - r["due"] for r in recs]
    assert min(late) >= 0
    # requests due during the stall went out late, and their latency
    # counts from the due time
    stalled = [r for r in recs[1:] if r["due"] < recs[0]["done"]]
    assert stalled and all(r["sent"] >= recs[0]["done"] - 1e-3
                           for r in stalled)
    assert all(r["done"] - r["due"] >= r["done"] - r["sent"]
               for r in recs)


def test_closed_loop_keeps_each_client_to_one_request():
    drv = CATALOG.module("traffic", "closed_loop")
    live, peak = Counter(), []

    async def query(i):
        live["n"] += 1
        peak.append(live["n"])
        await asyncio.sleep(0.01)
        live["n"] -= 1
        return i

    async def main():
        t0 = time.perf_counter()
        return await drv.drive(query, 8, {"clients": 4}, t0, 0.3, 3,
                               give_up_s=5.0)

    recs = asyncio.run(main())
    assert max(peak) == 4 and len(recs) >= 4 * 10
    assert all(r["done"] >= r["sent"] for r in recs)


def test_parts_are_found_by_name_in_new_files(tmp_path):
    """A cell, configuration, system, mix, driver, kernel and metric
    added as files, with an entry in BENCHMARK.json, need no edit
    elsewhere."""
    root = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, root, ignore=shutil.ignore_patterns(
        "__pycache__"))
    bench = json.loads(json.dumps(CATALOG.benchmark))
    bench["configs"].append({"name": "new-config", "source": "tests",
                             "file": "benchmarks/chip/configs/new.json",
                             "reduced": [], "why": "added by files"})
    (root / "configs" / "new.json").write_text(json.dumps(
        dict(CATALOG.config("colpali-hpc"), name="new-config",
             system="new_system")))
    (root / "systems" / "new_system.py").write_text(
        "CHIPS = (4,)\n\ndef build(*a):\n    return None, None\n\n"
        "def compile(*a, **k):\n    return {}\n")
    bench["workloads"].append({"name": "new.cell", "config": "new-config",
                               "traffic": "new-mix", "chips": 1,
                               "why": "added by files"})
    next(m for m in bench["end_to_end"]
         if m["name"] == "qps")["workloads"].append("new.cell")
    bench["per_layer"].append({"name": "new.metric", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "serving", "moves": "qps",
                               "workloads": ["new.cell"]})
    (root / "workloads" / "new.cell.json").write_text(json.dumps(
        {"pages": 4096, "chunk_pages": 4096, "queries": 64,
         "check_requests": 8, "warm_rungs": [64]}))
    (root / "traffic" / "new-mix.json").write_text(json.dumps(
        {"driver": "new_driver", "clients": 2}))
    (root / "traffic" / "new_driver.py").write_text(
        "async def drive(*a, **k):\n    return []\n")
    (root / "metrics" / "new.metric.py").write_text(
        "def read(run):\n    return 1.5\n")
    (root / "kernels" / "new_kernel.py").write_text(
        "PATTERN = 'new'\n\ndef search_counts(config, pages, real):\n"
        "    return 1, 1\n")
    cat = Catalog(str(root), bench)
    cell = cat.cell("new.cell")
    assert cell["workload"]["pages"] == 4096
    assert cell["config"]["name"] == "new-config"
    assert [m["name"] for m in cell["per_layer"]] == ["new.metric"]
    assert "qps" in [m["name"] for m in cell["end_to_end"]]
    assert cat.module("traffic", cell["mix"]["driver"]).drive
    assert cat.module("metrics", "new.metric").read(None) == 1.5
    assert cat.module("kernels", "new_kernel").search_counts(0, 0, 0)
    assert cat.system(cell["config"]).CHIPS == (4,)
    # a configuration that names no system is served on one chip
    one = cat.system(cat.config("colpali-hpc"))
    assert one.CHIPS == (1,) and one.build and one.compile


def test_every_cell_and_metric_has_its_files():
    for w in CATALOG.benchmark["workloads"]:
        cell = CATALOG.cell(w["name"])
        assert cell["end_to_end"] and cell["per_layer"]
        assert CATALOG.module("references", cell["config"]["reference"])
        assert CATALOG.module("traffic", cell["mix"]["driver"])
        assert {"setup_s", "index_bytes_per_page"} <= {
            m["name"] for m in cell["end_to_end"]}
    for m in CATALOG.benchmark["end_to_end"] + CATALOG.benchmark["per_layer"]:
        assert CATALOG.module("metrics", m["name"]).read
    for c in CATALOG.benchmark["configs"]:
        assert c["file"].startswith("benchmarks/chip/configs/")
        assert CATALOG.config(c["name"])["name"] == c["name"]


def test_split_metrics_share_one_reader():
    assert (CATALOG.module("metrics", "adc_roofline.open").read.__code__
            .co_code == CATALOG.module("metrics", "adc_roofline.closed")
            .read.__code__.co_code)
    assert CATALOG.module("metrics", "search.ms_per_query.closed").read


def test_runner_refuses_cpu_and_unknown_chips(capsys):
    peaks = CATALOG.json(".", "peaks")
    with pytest.raises(run_cell.RunRefused, match="no TPU"):
        run_cell.require_devices(1, peaks)
    tpu = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert run_cell.require_devices(1, peaks, [tpu]) == [tpu]
    with pytest.raises(run_cell.RunRefused, match="asks for 4"):
        run_cell.require_devices(4, peaks, [tpu])
    with pytest.raises(run_cell.RunRefused, match="JAX found 1"):
        run_cell.require_devices(4, peaks, [tpu], allowed=(1, 4))
    # four chips are there, but the cell's system serves on one
    with pytest.raises(run_cell.RunRefused, match=r"serves on \(1,\)"):
        run_cell.require_devices(4, peaks, [tpu] * 4)
    # a cell takes one chip or four
    with pytest.raises(run_cell.RunRefused, match="1 or 4"):
        run_cell.require_devices(2, peaks, [tpu] * 4, allowed=(1, 2, 4))
    assert run_cell.require_devices(4, peaks, [tpu] * 5,
                                    allowed=(1, 4)) == [tpu] * 4
    other = SimpleNamespace(platform="tpu", device_kind="TPU v9 ultra")
    with pytest.raises(run_cell.RunRefused, match="no peaks"):
        run_cell.require_devices(1, peaks, [other])
    with pytest.raises(run_cell.RunRefused, match="no peaks"):
        run_cell.require_devices(4, peaks, [tpu] * 3 + [other],
                                 allowed=(1, 4))
    rc = run_cell.main(["--workload", "colpali-hpc.scan-open", "--seed",
                        "1", "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "no TPU" in out.err


def test_peaks_table_names_its_source():
    peaks = CATALOG.json(".", "peaks")
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert max(v5e["ops_per_s"].values()) == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert math.isclose(v5e["hbm_bytes"], 16e9)



def test_qps_counts_queries_answered_in_the_window():
    run = SimpleNamespace(t0=0.0, t1=10.0, seconds=10.0, records=[
        {"sent": 0.0, "done": 4.0, "result": 1},
        {"sent": 4.0, "done": 8.0, "result": 1},
        {"sent": 8.0, "done": 12.0, "result": 1},    # half in the window
        {"sent": 9.0, "done": 9.5, "error": "x"},    # failed
        {"sent": 9.5, "done": None}])                # never answered
    assert CATALOG.module("metrics", "qps").read(run) == pytest.approx(0.25)
