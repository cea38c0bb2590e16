"""Closed loop: a fixed number of clients, each sending its next query
when its last is answered, until the window ends.

Mix parameters: `clients`. Client c's j-th query is drawn from the pool
by the seed, so the same seed sends the same queries in the same order.
A request is due when its client sends it; no request is sent after the
window closes, and those in flight then are awaited.
"""
from __future__ import annotations

import asyncio
import time

import numpy as np


async def drive(query, pool_size: int, mix: dict, t0: float,
                seconds: float, seed: int, give_up_s: float):
    """Run `mix["clients"]` clients through `query(pool index)`; returns
    one record per request: {q, due, sent, done, result | error}."""
    t_end = t0 + seconds
    records = []

    async def client(c):
        rng = np.random.default_rng([seed, 3, c])
        delay = t0 - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        while time.perf_counter() < t_end:
            now = time.perf_counter()
            rec = {"q": int(rng.integers(0, pool_size)), "due": now,
                   "sent": now}
            records.append(rec)
            try:
                rec["result"] = await query(rec["q"])
            except Exception as e:  # noqa: BLE001 - a failed request is data
                rec["error"] = repr(e)
            rec["done"] = time.perf_counter()

    tasks = [asyncio.create_task(client(c)) for c in range(mix["clients"])]
    _, pending = await asyncio.wait(
        tasks, timeout=max(0.0, t_end + give_up_s - time.perf_counter()))
    for t in pending:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    for rec in records:
        rec.setdefault("done", None)
    return records
