"""A tiny copy of the chip benchmark's catalog for CPU tests: the real
harness, traffic drivers, references and metric readers, with small
configurations and cells beside them."""
from __future__ import annotations

import json
import os
import shutil

from benchmarks.chip.catalog import HERE, Catalog

TINY_PAGES = {"n_topics": 6, "patches_per_topic": 8, "noise": 0.2,
              "salient_frac": 0.4, "page_protos": 4}
TINY = {
    "flat": {"reference": "adc_rerank",
             "hpc": {"k": 64, "p": 60.0, "prune_side": "doc",
                     "backend": "flat", "rerank": 8,
                     "kmeans_restarts": 4}},
    "hamming": {"reference": "hamming",
                "hpc": {"k": 64, "p": 60.0, "prune_side": "doc",
                        "backend": "hamming", "rerank": 0,
                        "kmeans_restarts": 4}},
}


def tiny_catalog(tmp, backend: str = "flat", system: str | None = None,
                 chips: int = 1) -> Catalog:
    """Catalog of cells `tiny.open` (open loop) and `tiny.closed`
    (closed loop) over 512 tiny pages (48 clusters of patches for 64
    codes, as the real configurations have 240 for 256 or 512), in a
    copy under `tmp`. With `system`, the configuration names that
    test-only system, `<system>_system.py` beside this file, copied in
    as `systems/<system>.py`; the cells ask for `chips` chips."""
    root = os.path.join(str(tmp), "benchmarks", "chip")
    shutil.copytree(HERE, root, ignore=shutil.ignore_patterns(
        "__pycache__"))
    extra = {}
    if system is not None:
        shutil.copy(os.path.join(os.path.dirname(__file__),
                                 f"{system}_system.py"),
                    os.path.join(root, "systems", f"{system}.py"))
        extra["system"] = system

    def put(kind, name, obj):
        with open(os.path.join(root, kind, f"{name}.json"), "w") as f:
            json.dump(obj, f)

    put("configs", "tiny", dict(
        TINY[backend], name="tiny",
        encoder={"n_patches": 32, "query_len": 8, "proj_dim": 32},
        top_k=16, max_batch=8, pages=TINY_PAGES, **extra))
    for cell in ("tiny.open", "tiny.closed"):
        put("workloads", cell, {"pages": 512, "chunk_pages": 256,
                                "queries": 64, "check_requests": 16,
                                "warm_rungs": [1, 2, 4, 8]})
    put("traffic", "t-open", {"driver": "open_loop", "rate_qps": 20.0,
                              "order_seed": 1})
    put("traffic", "t-closed", {"driver": "closed_loop", "clients": 8})
    bench = json.loads(json.dumps(Catalog().benchmark))
    bench["configs"] = [{"name": "tiny", "source": "tests",
                         "file": "benchmarks/chip/configs/tiny.json",
                         "reduced": [], "why": "tiny"}]
    bench["workloads"] = [
        {"name": "tiny.open", "config": "tiny", "traffic": "t-open",
         "chips": chips, "why": "tiny open loop"},
        {"name": "tiny.closed", "config": "tiny", "traffic": "t-closed",
         "chips": chips, "why": "tiny closed loop"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            opened = any(w.endswith("open") for w in m["workloads"])
            m["workloads"] = ["tiny.open" if opened else "tiny.closed"]
    return Catalog(root, bench)
