#!/usr/bin/env python3
"""Finds the highest rate an open-loop cell sustains, by a sweep on the
chip: the cell is set up once, then its mix is offered at each rate, in
each arrival order, for a window of its own.

  python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
      --seconds <s> --rates 4,6,8 [--orders 1,2]

Prints one JSON line per rate and order: offered and answered counts,
the share answered by the window's end plus one second, p50/p95 latency
from the due time, the backlog growth (mean latency of the last quarter
of requests over that of the first quarter), the batches per rung, and
the sender's lateness. A rate is sustained where, in every order, the
share is at least 0.99 and the growth under 2. The last line names the
knee: the highest rate sustained with every lower rate swept sustained
too. A cell's fixed rate is 0.7x the knee.
"""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def summary(run, rate: float) -> dict:
    import numpy as np

    from benchmarks.chip import readers

    lat = readers.latencies_ms(run)
    n = len(lat)
    answered = [r for r in run.records if "result" in r]
    in_time = sum(1 for r in answered if r["done"] <= run.t1 + 1.0)
    q = max(1, n // 4)
    late = [(r["sent"] - r["due"]) * 1e3 for r in run.records if "sent" in r]
    return {"rate_qps": rate, "offered": n, "answered": len(answered),
            "share": in_time / n if n else None,
            "p50_ms": readers.percentile(lat, 50),
            "p95_ms": readers.percentile(lat, 95),
            "growth": float(np.mean(lat[-q:]) / np.mean(lat[:q])),
            "batches": {str(b): v["batches"]
                        for b, v in run.server_stats["rungs"].items()},
            "late_p99_ms": readers.percentile(late, 99),
            "late_max_ms": max(late, default=None)}


def sustained(line: dict) -> bool:
    return line["share"] >= 0.99 and line["growth"] < 2.0


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--orders", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from benchmarks.chip import run_cell
    from benchmarks.chip.catalog import Catalog

    catalog = Catalog()
    devices = run_cell.cell_devices(catalog, args.workload)
    run_cell.enable_compile_cache()
    cell = run_cell.Cell(catalog, args.workload, args.seed, annotate=False,
                         devices=devices)
    print(json.dumps({"setup_phases_s": cell.phases.seconds}), flush=True)
    orders = [int(o) for o in args.orders.split(",") if o] or [
        cell.mix["order_seed"]]
    knee, broken = None, False
    for rate in (float(r) for r in args.rates.split(",")):
        ok = True
        for order in orders:
            run = cell.window(dict(cell.mix, rate_qps=rate,
                                   order_seed=order),
                              args.seconds, args.seed, False)
            line = dict(summary(run, rate), order_seed=order)
            ok = ok and sustained(line)
            print(json.dumps(line), flush=True)
        broken = broken or not ok
        if not broken:
            knee = rate
    print(json.dumps({"knee_qps": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
