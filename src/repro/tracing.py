"""Spans and counters of the served search path, kept in memory.

A `Tracer` holds span records in a bounded ring and named integer
counters. A span record (`Span`) is a name, its start and end on
`time.perf_counter_ns`, its own id, its parent's id (or None) and the
ids it carries (request ids, batch id, rung), so the spans of one
request or batch can be joined after the fact.

Two ways to record a span:

* ``with tracer.span(name, parent=..., **ids):`` times a scoped block on
  one thread. Its parent defaults to the innermost scoped span open on
  the same thread. With ``annotate=True`` the block is also a
  ``jax.profiler.TraceAnnotation`` of the same name carrying the same
  ids, so a profiler trace shows it on the host's timeline.
* ``tracer.mark(name, start_ns, end_ns, ...)`` records a span whose
  start and end were stamped elsewhere: one that straddles an ``await``
  or crosses threads (a request's wait in the queue). Such a span is
  never an annotation: coroutines share the event loop's thread, so the
  profiler's nested events would be mis-nested.

With ``annotate=False`` (the default) no annotation object is made: a
span costs two clock reads and one tuple append.

The ring holds `CAPACITY` records (2^18). The served search path records
two spans a request and seven a batch, so the ring holds the last ~29,000
requests served one to a batch (nine records each), and the last
~120,000 of full 64-query batches: a 51 s window at up to ~570 or
~2,400 queries/s. Older records drop off the ring; a counter counts on
until it is dropped.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import Dict, Iterator, List, NamedTuple, Optional

CAPACITY = 1 << 18


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    id: int
    parent: Optional[int]
    ids: dict

    @property
    def ms(self) -> float:
        return (self.end_ns - self.start_ns) / 1e6


class Tracer:
    """Span records in a bounded ring, and named counters."""

    def __init__(self, annotate: bool = False):
        self.annotate = bool(annotate)
        self._ring: collections.deque = collections.deque(maxlen=CAPACITY)
        self._next_id = itertools.count(1).__next__
        self._local = threading.local()
        self._lock = threading.Lock()
        self.counters: Dict[str, int] = {}

    # -- spans ----------------------------------------------------------------

    def new_id(self) -> int:
        """A fresh span id: reserve one for a span recorded later by
        `mark`, so that its children can name it as their parent."""
        return self._next_id()

    @contextlib.contextmanager
    def span(self, name: str, parent: Optional[int] = None,
             **ids) -> Iterator[int]:
        """Time the enclosed block as span `name`; yields its id."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        if parent is None and stack:
            parent = stack[-1]
        sid = self._next_id()
        stack.append(sid)
        note = None
        if self.annotate:
            import jax

            note = jax.profiler.TraceAnnotation(name, **ids)
            note.__enter__()
        t0 = time.perf_counter_ns()
        try:
            yield sid
        finally:
            t1 = time.perf_counter_ns()
            if note is not None:
                note.__exit__(None, None, None)
            stack.pop()
            self._ring.append((name, t0, t1, sid, parent, ids))

    def mark(self, name: str, start_ns: int, end_ns: int,
             parent: Optional[int] = None, span_id: Optional[int] = None,
             **ids) -> int:
        """Record span `name` from stamps taken elsewhere; returns its
        id (`span_id` if one was reserved with `new_id`)."""
        sid = self._next_id() if span_id is None else span_id
        self._ring.append((name, start_ns, end_ns, sid, parent, ids))
        return sid

    def records(self, name: Optional[str] = None,
                since_ns: Optional[int] = None) -> List[Span]:
        """The records in the ring, oldest first: those named `name`,
        ending at or after `since_ns`, if given."""
        out = list(self._ring)           # (name, start, end, id, ...)
        if name is not None:
            out = [t for t in out if t[0] == name]
        if since_ns is not None:
            out = [t for t in out if t[2] >= since_ns]
        return [Span._make(t) for t in out]

    # -- counters -------------------------------------------------------------

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + int(n)

    def drop_counters(self, *names: str) -> None:
        """Drop the counters `names` (they count from 0 again)."""
        with self._lock:
            for name in names:
                self.counters.pop(name, None)
