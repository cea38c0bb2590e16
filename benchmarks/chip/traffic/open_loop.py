"""Open loop: requests sent on an absolute schedule, whatever the server
does.

Mix parameters: `rate_qps`, `order_seed`. The window of `seconds` holds
n = round(rate_qps * seconds) requests. Their gaps are the n quantiles
of the exponential distribution at that rate (a Poisson process's gaps),
scaled to span the window, in the order `order_seed` draws. The order is
the mix's and not the run's: at 0.7x the knee the order of the same gaps
moves the 95th percentile by up to 80% from seed to seed, far more than
two runs of one seed differ, so every seed offers the same arrivals and
the run's seed draws the queries (and the corpus). Request i is due at
t0 + (sum of the first i + 1 gaps); it is sent at its due time or, if
the loop runs late, as soon as it can, and its latency is counted from
the due time, so a stall of the sender is not hidden.
"""
from __future__ import annotations

import asyncio
import time

import numpy as np


def schedule(rate_qps: float, seconds: float, order_seed: int
             ) -> np.ndarray:
    """Due offsets (s) from the window start, increasing, all < seconds."""
    n = max(1, round(rate_qps * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate_qps
    gaps *= seconds * (n - 0.5) / n / gaps.sum()
    return np.cumsum(np.random.default_rng(order_seed).permutation(gaps))


async def drive(query, pool_size: int, mix: dict, t0: float,
                seconds: float, seed: int, give_up_s: float):
    """Send the schedule through `query(pool index)`; returns one record
    per request: {q, due, sent, done, result | error}."""
    due = t0 + schedule(mix["rate_qps"], seconds, mix["order_seed"])
    picks = np.random.default_rng([seed, 2]).integers(0, pool_size,
                                                      len(due))
    records = [{"q": int(q), "due": float(d)} for q, d in zip(picks, due)]

    async def one(rec):
        rec["sent"] = time.perf_counter()
        try:
            rec["result"] = await query(rec["q"])
        except Exception as e:  # noqa: BLE001 - a failed request is data
            rec["error"] = repr(e)
        rec["done"] = time.perf_counter()

    tasks = []
    for rec in records:
        delay = rec["due"] - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.create_task(one(rec)))
    _, pending = await asyncio.wait(
        tasks, timeout=max(0.0, t0 + seconds + give_up_s
                           - time.perf_counter()))
    for t in pending:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    return records
