"""The served search path's own spans, read beside the device trace.

A run that carries the server's tracer records (`run.serve_records`,
`repro.tracing.Span`s on `time.perf_counter_ns`) can be read on its own
(waits, gaps between batches) or, once mapped onto the profiler's clock
by `clock_offset`, against the device trace (`run.trace`, a
`spantrace.ScopedTrace`). Each function returns None where the run holds
nothing to read: no records (a program without a tracer), or no trace
(`--trace 0`, or no device plane).
"""
from __future__ import annotations

import numpy as np

# the scoped spans (one thread, no await) that are also profiler
# annotations, matched by batch id to measure the clock offset
MATCHED = ("serve.stage", "serve.compute")


def records(run, name: str | None = None):
    recs = getattr(run, "serve_records", None)
    if not recs:
        return None
    return [r for r in recs if name is None or r.name == name]


def clock_offset(recs, trace):
    """(offset ns, residuals ns) that map the tracer's clock onto the
    trace's: the median over the `MATCHED` spans present in both, paired
    by name and batch id, of (trace start - record start), and each
    pair's difference from it. None where no pair matches."""
    in_trace = {}
    for name in MATCHED:
        for start, _, ids in trace.host_spans(name):
            if "batch" in ids:
                in_trace[(name, int(ids["batch"]))] = start
    diffs = [in_trace[(r.name, r.ids["batch"])] - r.start_ns
             for r in recs if r.name in MATCHED
             and (r.name, r.ids.get("batch")) in in_trace]
    if not diffs:
        return None
    offset = int(np.median(diffs))
    return offset, [d - offset for d in diffs]


def computes(run):
    """[(start ns, end ns, batch id)] of the `serve.compute` spans, by
    start, on the tracer's clock."""
    recs = records(run, "serve.compute")
    if recs is None:
        return None
    return sorted((r.start_ns, r.end_ns, r.ids["batch"]) for r in recs)


def own_device_starts(run):
    """{batch id: ns} when each batch's search could first have the
    device: the start of its `serve.compute`, or the end of the previous
    batch's, whichever is later (two batches may be in flight)."""
    comp = computes(run)
    if comp is None:
        return None
    out, prev_end = {}, None
    for start, end, batch in comp:
        out[batch] = start if prev_end is None else max(start, prev_end)
        prev_end = end if prev_end is None else max(prev_end, end)
    return out


def waits_ns(run):
    """[(enqueue ns, own device start ns)] of each request answered in
    the window (its `serve.request` ending inside it)."""
    starts = own_device_starts(run)
    if starts is None:
        return None
    lo, hi = run.t0 * 1e9, run.t1 * 1e9
    return [(r.start_ns, starts[r.ids["batch"]])
            for r in records(run, "serve.request")
            if lo <= r.end_ns <= hi and r.ids["batch"] in starts]


def wait_p95_ms(run):
    waits = waits_ns(run)
    if not waits:
        return None
    return float(np.percentile([(d - e) / 1e6 for e, d in waits], 95))


def batch_gaps_ns(run):
    """[(this compute's end, next compute's start)] of consecutive
    batches whose computes both start in the window, on the tracer's
    clock."""
    comp = computes(run)
    if comp is None:
        return None
    lo, hi = run.t0 * 1e9, run.t1 * 1e9
    inside = [c for c in comp if lo <= c[0] < hi]
    return [(a[1], b[0]) for a, b in zip(inside, inside[1:])]


def gap_ms_per_batch(run):
    gaps = batch_gaps_ns(run)
    if not gaps:
        return None
    return float(np.mean([(b - a) / 1e6 for a, b in gaps]))


# the stages a gap between batches is split into, in the order a
# nanosecond covered by several is given to the first
GAP_STAGES = ("serve.d2h", "serve.fanout", "serve.coalesce", "serve.slot",
              "serve.stage")


def gap_split_ms(run):
    """{stage: ms per gap} of the gaps between batches (a batch that
    starts computing before the previous one ends leaves none): the
    part of each gap inside each `GAP_STAGES` span, then `serve.batch`
    (inside a batch but in no stage: hand-offs between the loop and the
    executor), then `unspanned` (in no server span: the clients'
    turnaround)."""
    gaps = [(a, b) for a, b in batch_gaps_ns(run) or () if b > a]
    if not gaps:
        return None
    recs = records(run)
    spans = {n: [(r.start_ns, r.end_ns) for r in recs if r.name == n]
             for n in GAP_STAGES + ("serve.batch",)}
    total = {n: 0 for n in GAP_STAGES + ("serve.batch", "unspanned")}
    for a, b in gaps:
        left = [(a, b)]
        for n in GAP_STAGES + ("serve.batch",):
            covered, rest = _cut(left, spans[n])
            total[n] += covered
            left = rest
        total["unspanned"] += sum(e - s for s, e in left)
    return {n: v / len(gaps) / 1e6 for n, v in total.items()}


def _cut(pieces, spans):
    """(ns of `pieces` inside the union of `spans`, what is left)."""
    covered, rest = 0, []
    for s, e in pieces:
        cur = [(s, e)]
        for a, b in spans:
            if b <= s or a >= e:
                continue
            nxt = []
            for x, y in cur:
                lo, hi = max(x, a), min(y, b)
                if lo >= hi:
                    nxt.append((x, y))
                    continue
                covered += hi - lo
                if x < lo:
                    nxt.append((x, lo))
                if hi < y:
                    nxt.append((hi, y))
            cur = nxt
        rest.extend(cur)
    return covered, rest


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return out


def _overlap_ns(a, b) -> int:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_with_work_pct(run):
    """Share of the traced window, in %, in which no op ran on the chip
    while some request was between its enqueue and its batch's own
    device start (requests' waits mapped onto the trace's clock)."""
    trace = getattr(run, "trace", None)
    offset = getattr(run, "clock_offset_ns", None)
    waits = waits_ns(run)
    if trace is None or not trace.ops or offset is None or not waits:
        return None
    lo, hi = trace.window()
    waiting = _union([(max(lo, e + offset), min(hi, d + offset))
                      for e, d in waits])
    _, busy = trace.busy(lo, hi)
    idle_waiting = (sum(e - s for s, e in waiting)
                    - _overlap_ns(waiting, busy))
    return 100.0 * idle_waiting / (hi - lo)


def stage_pct(run, stage: str):
    """Device self time of the ops under program scope `stage`, over
    device busy time in the traced window, in %."""
    trace = getattr(run, "trace", None)
    if trace is None or not any(getattr(trace, "scopes", ())):
        return None
    lo, hi = trace.window()
    busy = trace.busy(lo, hi)[0]
    return 100.0 * trace.stage_ns(stage, lo, hi) / busy if busy else None


def on_trace_clock(run):
    """[(name, start, end)] of the tracer's spans, moved onto the trace's
    clock by the measured offset."""
    offset = getattr(run, "clock_offset_ns", None)
    recs = records(run)
    if offset is None or recs is None:
        return None
    return [(r.name, r.start_ns + offset, r.end_ns + offset) for r in recs]


def idle_by_span(run, min_ns: int = 100_000):
    """{span name: idle seconds} over the traced window's idle gaps of
    `min_ns` or more, each gap given to the innermost `bench.*` span of
    the trace or span of the tracer (on the trace's clock) open at its
    middle; the shorter gaps, between ops of one search, under
    "(shorter gaps)"."""
    spans = on_trace_clock(run)
    trace = getattr(run, "trace", None)
    if spans is None or trace is None or not trace.ops:
        return None
    spans = [s for s in trace.spans if s[0].startswith("bench.")] + spans
    lo, hi = trace.window()
    _, busy = trace.busy(lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    out = {}
    for s, e in zip(edges[::2], edges[1::2]):
        if e - s < min_ns:
            name = "(shorter gaps)"
        else:
            mid = (s + e) // 2
            open_ = [sp for sp in spans if sp[1] <= mid < sp[2]]
            name = (min(open_, key=lambda sp: sp[2] - sp[1])[0]
                    if open_ else "outside the window's spans")
        out[name] = out.get(name, 0.0) + (e - s) / 1e9
    return out
