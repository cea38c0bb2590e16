"""Reduction of a JAX profiler trace to device busy time, idle gaps and
kernel time.

`Trace.from_profile` reads the `.xplane.pb` the profiler wrote into one
`Trace` for each chip of a cell: the events of the chip's op line, one
per HLO op (a `while` op encloses the ops of its body), each named by
its HLO instruction, and the benchmark's own host spans (`bench.*`
`TraceAnnotation`s). Everything after that works on those plain lists,
so the reduction is checked on a recorded trace (`Trace.to_json` /
`Trace.from_json`) without a chip.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"


def op_name(event_name: str) -> str:
    """`quantized_maxsim_pallas.6` of an event named by its HLO text,
    `%quantized_maxsim_pallas.6 = f32[...] custom-call(...)`."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


@dataclass
class Trace:
    # (op name, start ns, end ns) of each op on the chip's op line
    ops: list = field(default_factory=list)
    # (name, start ns, end ns) of each `bench.*` host span
    spans: list = field(default_factory=list)

    # -- reading ------------------------------------------------------------

    @classmethod
    def from_profile(cls, path: str, devices):
        """One trace per chip named in `devices` (`/device:TPU:<id>`), in
        that order, from one read of the profile; each holds every
        `bench.*` host span."""
        from jax.profiler import ProfileData

        prof = ProfileData.from_file(path)
        ops, spans = {d: [] for d in devices}, []
        for plane in prof.planes:
            if plane.name in ops:
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    for e in line.events:
                        start = int(e.start_ns)
                        ops[plane.name].append((op_name(e.name), start,
                                                start + int(e.duration_ns)))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith("bench."):
                            spans.append((e.name, int(e.start_ns),
                                          int(e.start_ns
                                              + e.duration_ns)))
        spans.sort(key=lambda s: s[1])
        return [cls(sorted(ops[d], key=lambda o: o[1]), list(spans))
                for d in devices]

    def to_json(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump({"ops": self.ops, "spans": self.spans}, f)

    @classmethod
    def from_json(cls, path: str):
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return cls([tuple(o) for o in d["ops"]],
                   [tuple(s) for s in d["spans"]])

    # -- reduction ----------------------------------------------------------

    def window(self):
        """(start, end) ns of the measured window (`bench.window`)."""
        w = [s for s in self.spans if s[0] == "bench.window"]
        if len(w) != 1:
            raise ValueError(f"{len(w)} bench.window spans in the trace")
        return w[0][1], w[0][2]

    def busy(self, lo: int, hi: int):
        """Union of the op intervals clipped to [lo, hi]: (busy ns,
        sorted disjoint intervals)."""
        merged = []
        for _, s, e in self.ops:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return sum(e - s for s, e in merged), merged

    def kernel_ns(self, pattern: str, lo: int | None = None,
                  hi: int | None = None) -> int:
        """Device time of the ops whose own name holds a match of
        `pattern` (a regex), clipped to [lo, hi] where given."""
        rx = re.compile(pattern)
        return sum(e - s if lo is None else max(0, min(e, hi) - max(s, lo))
                   for name, s, e in self.ops if rx.search(name))

    def op_self_ns(self, lo: int, hi: int) -> dict:
        """{op name: self time in ns} in [lo, hi], numbered instances
        summed: an op's time less that of the ops it encloses, so a
        `while` counts only its own gaps."""
        self_ns, stack = {}, []
        for name, s, e in sorted(self.ops, key=lambda o: (o[1], -o[2])):
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            while stack and stack[-1][1] <= s:
                stack.pop()
            key = re.sub(r"[.]\d+$", "", name)
            self_ns[key] = self_ns.get(key, 0) + (e - s)
            if stack and e <= stack[-1][1]:     # enclosed, not overlapping
                self_ns[stack[-1][0]] -= e - s
            stack.append((key, e))
        return self_ns

    def idle_gaps(self, lo: int, hi: int, n: int = 10):
        """[[host span, seconds]] of the n longest gaps in [lo, hi] in
        which no op ran, each named by the innermost `bench.*` host span
        open at its middle (`bench.window`: the host was in none of the
        benchmark's calls into the search)."""
        _, merged = self.busy(lo, hi)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1])
                for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            mid = (s + e) // 2
            open_ = [sp for sp in self.spans if sp[1] <= mid < sp[2]]
            name = (min(open_, key=lambda sp: sp[2] - sp[1])[0]
                    if open_ else "outside the window's spans")
            out.append([name, (e - s) / 1e9])
        return out


def top_ops(traces, lo: int, hi: int, n: int = 10):
    """[[op name, seconds]] of the n ops with the most self time in
    [lo, hi] (`Trace.op_self_ns`), summed over `traces` (one a chip)
    under the same name."""
    total = {}
    for t in traces:
        for name, ns in t.op_self_ns(lo, hi).items():
            total[name] = total.get(name, 0) + ns
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def find_profile(log_dir: str) -> str:
    """The newest `.xplane.pb` under a profiler log directory."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)
