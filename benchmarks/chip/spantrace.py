"""A profiler trace with the program's own host spans and device scopes.

`ScopedTrace` is a `trace.Trace` that keeps two things more:

- the server's host spans, the `serve.*` annotations of a
  `repro.tracing.Tracer` built with `annotate=True`, beside the
  benchmark's `bench.*` ones in `spans`, and the ids each carries (batch,
  rung) in `span_ids`, a list parallel to `spans`;
- each device op's program scope in `scopes`, a list parallel to `ops`:
  the `jax.named_scope` path of its HLO instruction, such as
  `search.scan/scan.merge` (a fused op takes its root's scope), or "" for
  an op outside every scope. The chip's op events carry no op path, so
  the scope is looked up in the compiled programs' HLO text, by the
  instruction's name and result shape: every rung's program is named
  `jit__lambda` and reuses instruction names, but its shapes hold the
  rung.

`ops` and `spans` keep their 3-tuples, so everything `Trace` computes
reads as before; `idle_gaps` names a gap by the innermost `bench.*` or
`serve.*` span open at its middle. A trace written by `Trace.to_json`
loads with no scopes and no ids.
"""
from __future__ import annotations

import gzip
import json
import re
from dataclasses import dataclass, field

from benchmarks.chip.trace import OPS_LINE, Trace, op_name

HOST_SPANS = ("bench.", "serve.")
# the program's scopes (`search.scan`, `scan.merge`, `kernel.layout`); the
# op path's other components are JAX's own: `jit(f)`, `while`, `body`, a
# primitive, or an argument's path (`st.codebook`)
SCOPE_PREFIXES = ("search.", "scan.", "kernel.")
# `%name = <result shape> opcode(...`, in HLO text and in op event names
_INSTR = re.compile(r'^\s*(?:ROOT )?%(\S+) = (.*?) [a-z][\w-]*\(')
_OP_PATH = re.compile(r'op_name="([^"]*)"')


def scope_of(op_path: str) -> str:
    """`search.scan/scan.merge` of
    `jit(f)/search.scan/while/body/closed_call/scan.merge/sort`."""
    return "/".join(c for c in op_path.split("/")
                    if c.startswith(SCOPE_PREFIXES))


def instruction(text: str) -> tuple:
    """(name, result shape) of an HLO instruction's text, such as an op
    event's name `%sort.9 = (f32[1,384]{...}, s32[1,384]{...}) sort(...`;
    (text, "") for text of another form."""
    m = _INSTR.match(text)
    return (m.group(1), m.group(2)) if m else (text, "")


def hlo_scopes(hlo_text: str) -> dict:
    """{(instruction name, result shape): scope} of a compiled program's
    HLO text (`compiled.as_text()`), from each instruction's
    `metadata.op_name`."""
    out = {}
    for line in hlo_text.splitlines():
        m, path = _INSTR.match(line), _OP_PATH.search(line)
        if m and path:
            out[m.group(1), m.group(2)] = scope_of(path.group(1))
    return out


@dataclass
class ScopedTrace(Trace):
    # program scope of each op, parallel to `ops`
    scopes: list = field(default_factory=list)
    # ids carried by each host span ({} for `bench.*`), parallel to `spans`
    span_ids: list = field(default_factory=list)
    # the last `self_ns` computed, by its bounds
    _memo: dict = field(default_factory=dict, init=False, repr=False,
                        compare=False)

    # -- reading ------------------------------------------------------------

    @classmethod
    def from_profile(cls, path: str, devices, hlo: dict | None = None):
        """One trace per chip named in `devices`, in that order, from one
        read of the `.xplane.pb` at `path`: the chip's op line and every
        host span. An op's scope is looked up in `hlo` (`hlo_scopes` of
        the programs that ran)."""
        from jax.profiler import ProfileData

        hlo = hlo or {}
        prof = ProfileData.from_file(path)
        ops, spans = {d: [] for d in devices}, []
        by_event = {}                 # event name -> (op name, scope)
        for plane in prof.planes:
            if plane.name in ops:
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    for e in line.events:
                        name = e.name
                        got = by_event.get(name)
                        if got is None:
                            got = by_event[name] = (
                                op_name(name),
                                hlo.get(instruction(name), ""))
                        start = int(e.start_ns)
                        ops[plane.name].append((got[0], start,
                                                start + int(e.duration_ns),
                                                got[1]))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(HOST_SPANS):
                            start = int(e.start_ns)
                            ids = ({k: v for k, v in e.stats
                                    if isinstance(v, (int, float))}
                                   if e.name.startswith("serve.") else {})
                            spans.append((e.name, start,
                                          start + int(e.duration_ns), ids))
        spans.sort(key=lambda s: s[1])
        out = []
        for d in devices:
            chip = sorted(ops[d], key=lambda o: o[1])
            out.append(cls([o[:3] for o in chip], [s[:3] for s in spans],
                           [o[3] for o in chip], [s[3] for s in spans]))
        return out

    def to_json(self, path: str) -> None:
        names = sorted(set(self.scopes))
        index = {s: i for i, s in enumerate(names)}
        with gzip.open(path, "wt") as f:
            json.dump({"ops": self.ops, "spans": self.spans,
                       "scope_names": names,
                       "scopes": [index[s] for s in self.scopes],
                       "span_ids": self.span_ids}, f)

    @classmethod
    def from_json(cls, path: str):
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        ops = [tuple(o) for o in d["ops"]]
        spans = [tuple(s) for s in d["spans"]]
        names = d.get("scope_names")
        scopes = ([names[i] for i in d["scopes"]] if names is not None
                  else [""] * len(ops))
        return cls(ops, spans, scopes,
                   d.get("span_ids") or [{} for _ in spans])

    # -- reduction ----------------------------------------------------------

    def self_ns(self, lo: int, hi: int) -> list:
        """Self time in [lo, hi] of each op, by its index in `ops`: its
        time less that of the ops it encloses (as `Trace.op_self_ns`).
        Kept for the next call with the same bounds."""
        if (lo, hi) not in self._memo:
            self._memo.clear()
            self._memo[(lo, hi)] = self._self_ns(lo, hi)
        return self._memo[(lo, hi)]

    def _self_ns(self, lo: int, hi: int) -> list:
        out = [0] * len(self.ops)
        stack = []
        order = sorted(range(len(self.ops)),
                       key=lambda i: (self.ops[i][1], -self.ops[i][2]))
        for i in order:
            _, s, e = self.ops[i]
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            while stack and stack[-1][1] <= s:
                stack.pop()
            out[i] += e - s
            if stack and e <= stack[-1][1]:   # enclosed, not overlapping
                out[stack[-1][0]] -= e - s
            stack.append((i, e))
        return out

    def scope_ns(self, lo: int, hi: int) -> dict:
        """{scope: device self time in ns} in [lo, hi] ("" outside every
        scope)."""
        out = {}
        for scope, ns in zip(self.scopes, self.self_ns(lo, hi)):
            if ns:
                out[scope] = out.get(scope, 0) + ns
        return out

    def stage_ns(self, stage: str, lo: int, hi: int) -> int:
        """Device self time in [lo, hi] of the ops whose scope path holds
        `stage` (such as `scan.merge`)."""
        return sum(ns for scope, ns in self.scope_ns(lo, hi).items()
                   if stage in scope.split("/"))

    def attributed_share(self, lo: int, hi: int, patterns=()) -> float:
        """Share of the device self time in [lo, hi] whose op carries a
        program scope or has a name matching one of `patterns` (the
        kernels' `PATTERN`s)."""
        rx = [re.compile(p) for p in patterns]
        total = named = 0
        for (name, _, _), scope, ns in zip(self.ops, self.scopes,
                                           self.self_ns(lo, hi)):
            total += ns
            if scope or any(r.search(name) for r in rx):
                named += ns
        return named / total if total else 0.0

    def host_spans(self, name: str):
        """[(start, end, ids)] of the host spans named `name`."""
        return [(s[1], s[2], ids) for s, ids in zip(self.spans,
                                                    self.span_ids)
                if s[0] == name]
