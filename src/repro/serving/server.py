"""Async continuous-batching retrieval serving (v2).

`AsyncRetrievalServer` is asyncio-native: clients ``await server.query(...)``;
a coalescing loop drains the request queue under ``max_wait_ms`` and pads each
batch up a **power-of-two ladder** of compiled shapes (B in {1, 2, 4, ...,
max_batch}) instead of always padding to ``max_batch`` — a batch of 3 pads to
4, not 32, so a lone straggler pays single-digit-row compute. Shapes are
warmed lazily (jax.jit's shape-keyed cache compiles each (B, Mq) on first
use); ``warm_shapes`` pre-compiles the whole ladder up front.

Host staging overlaps device compute by double-buffering: the dispatcher
stages batch n+1's numpy->device transfer on the event loop while batch n's
jitted search runs in a bounded executor; ``jax.block_until_ready`` happens
only at fan-out, off the event loop, so percentiles include device time but
the loop never blocks on it.

Fault tolerance (v3, opt-in via ``ServeConfig.resilience``) threads the
`repro.serving.resilience` controllers through the loop: per-request
deadlines (expired items are dropped before staging and cancelled at
fan-out), bounded admission with explicit `Overloaded` rejection and
per-SLO-class token buckets, a degradation ladder that serves overload
bursts from pre-compiled cheaper search functions (`degraded_fns` — the
cascade's smaller (p1, p2) rungs down to hamming-only) and steps back up
under hysteresis, a watchdog that restarts a dead/hung dispatcher and
fails its claimed requests with `DispatcherFailed`, and a `FaultInjector`
with named sites (dispatch/stage/compute/fanout) driving the chaos suite.
Every successful response is a `Served` tuple tagged with the degradation
level that produced it. The degraded functions are part of the recompile
sentry's declared signature set — shedding and degrading never mint an
off-ladder compile.

`RetrievalServer` is the thin sync facade (thread-backed event loop) kept so
v1 call sites — ``submit`` returning a waitable request, blocking ``query`` —
keep working unchanged. ``close`` drains: in-flight batches complete and
deliver real results; requests still queued get a terminal `ServerClosed`
error instead of hanging until their client-side timeout. A facade
``query`` that times out *cancels* its queued item (and counts it in
``stats()["timeouts"]``) so abandoned requests stop occupying batch slots.

Every request and batch is recorded as spans on a `repro.tracing.Tracer`
(``tracer=``; by default one of its own, with no profiler annotations):
``serve.request`` and ``serve.queue`` per request, ``serve.batch`` and its
stages ``serve.coalesce`` / ``serve.slot`` / ``serve.stage`` /
``serve.compute`` / ``serve.d2h`` / ``serve.fanout`` per batch, plus the
counters ``serve.timeouts``, ``serve.deadline_expired``,
``serve.watchdog_restarts`` and ``serve.onehot_shared_queries`` (the real
queries of batches whose rung the ADC kernel scores in query groups,
`kernels.quantized_maxsim.traced_group`). ``stats()`` reads its latency
percentiles (p50/p99, per request, the paper's Table IV definitions),
mean batch, qps and per-ladder-rung batch occupancy from those records,
and its resilience counts from those counters.
"""
from __future__ import annotations

import asyncio
import collections
import dataclasses
import logging
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import quantized_maxsim as qmaxsim_k
from repro.tracing import Tracer
from repro.serving.resilience import (AdmissionController,
                                      DeadlineExceeded,
                                      DegradationController,
                                      DispatcherFailed, FaultInjector,
                                      Overloaded, ResilienceConfig)

logger = logging.getLogger(__name__)


class ServerClosed(RuntimeError):
    """Terminal error set on requests the server will never serve."""


class Served(tuple):
    """A ``(scores, ids)`` result tagged with the degradation level that
    served it (0 = full quality). Unpacks as a plain 2-tuple, so existing
    ``scores, ids = await server.query(...)`` call sites are unchanged."""

    def __new__(cls, pair, level: int = 0):
        self = tuple.__new__(cls, pair)
        self.level = int(level)
        return self


def padding_ladder(max_batch: int) -> Tuple[int, ...]:
    """Powers of two up to ``max_batch`` (always ending at ``max_batch``)."""
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    rungs: List[int] = []
    b = 1
    while b < max_batch:
        rungs.append(b)
        b *= 2
    rungs.append(max_batch)
    return tuple(rungs)


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 8
    max_wait_ms: float = 2.0
    top_k: int = 10
    # Compiled batch shapes. None -> power-of-two ladder up to max_batch;
    # a single-element tuple like (max_batch,) reproduces the v1 behaviour
    # of padding every batch to one full compiled shape.
    ladder: Optional[Tuple[int, ...]] = None
    # Double-buffer depth: how many staged batches may be in flight on the
    # device at once. 2 = stage n+1 while n computes (the default); 1
    # disables the overlap.
    max_inflight: int = 2
    # Wrap search_fn in a repro.analysis RecompileSentry: every call's
    # (B, Mq, dtypes, level) signature is recorded, batches whose B is not
    # a ladder rung raise RecompileGuardError instead of silently minting a
    # new compiled shape, and `recompile_report()` exposes the signature
    # set for the exact-rung-set assertion in tests/soaks.
    guard_recompiles: bool = False
    # Fault-tolerant serving (docs/design.md §11): deadlines, bounded
    # admission + load shedding, degradation ladder, watchdog. None keeps
    # the pre-v3 behaviour (unbounded queue, no deadlines, no watchdog).
    resilience: Optional[ResilienceConfig] = None

    def resolved_ladder(self) -> Tuple[int, ...]:
        if self.ladder is None:
            return padding_ladder(self.max_batch)
        rungs = tuple(sorted(set(int(b) for b in self.ladder)))
        if not rungs or rungs[0] < 1:
            raise ValueError(f"invalid ladder {self.ladder}")
        if rungs[-1] != self.max_batch:
            raise ValueError(
                f"ladder {rungs} must end at max_batch={self.max_batch}"
            )
        return rungs


class _Item:
    """One queued query inside the asyncio server."""

    __slots__ = ("q_emb", "q_mask", "q_sal", "future", "t_enqueue_ns",
                 "t_claim_ns", "span_id", "deadline", "slo")

    def __init__(self, q_emb, q_mask, q_sal, future, t_enqueue_ns, span_id,
                 deadline=None, slo="interactive"):
        self.q_emb, self.q_mask, self.q_sal = q_emb, q_mask, q_sal
        self.future = future
        # time.perf_counter_ns() at enqueue
        self.t_enqueue_ns = t_enqueue_ns
        self.t_claim_ns = None    # when the dispatcher took it (ns)
        # the request's id: its `serve.request` span, recorded at fan-out
        self.span_id = span_id
        # absolute time.perf_counter() deadline, or None
        self.deadline = deadline
        self.slo = slo


_STOP = object()


class AsyncRetrievalServer:
    """search_fn(q_emb (B,Mq,D), q_mask, q_sal) -> (scores (B,k), ids).

    Bind to one event loop: the first ``query`` (or an explicit ``start``)
    captures the running loop; all queries must come from that loop.

    ``degraded_fns`` is an ordered sequence of cheaper search functions
    (same signature/output shapes as ``search_fn``); level L > 0 of the
    degradation ladder serves from ``degraded_fns[L - 1]``. They must be
    pre-compiled shapes of the same ladder (see `LiveIndexSession` /
    `cascade.degrade_rungs`) so stepping down never compiles.

    ``tracer`` receives the server's spans and counters (module
    docstring); None gives the server a `Tracer` of its own with
    ``annotate=False``.
    """

    def __init__(self, search_fn: Callable, cfg: ServeConfig,
                 degraded_fns: Sequence[Callable] = (),
                 tracer: Optional[Tracer] = None):
        self.search_fns: List[Callable] = [search_fn, *degraded_fns]
        self.cfg = cfg
        self.tracer = tracer if tracer is not None else Tracer()
        self.ladder = cfg.resolved_ladder()
        self.recompile_sentry = None
        if cfg.guard_recompiles:
            from repro.analysis.recompile import RecompileSentry
            rungs = set(self.ladder)

            def _serve(q, qm, qs, level=0):
                return self.search_fns[level](q, qm, qs)

            def _cache_size():
                return sum(fn._cache_size()
                           for fn in self.search_fns
                           if hasattr(fn, "_cache_size"))

            _serve._cache_size = _cache_size

            def serve_signature(q, qm, qs, level=0):
                # B stays at position 0: tests and reports key rungs off
                # sig[0]; the degradation level rides at the end
                return (int(q.shape[0]), int(q.shape[1]), str(q.dtype),
                        str(qm.dtype), str(qs.dtype), int(level))

            n_levels = len(self.search_fns)
            self.recompile_sentry = RecompileSentry(
                _serve, name="serve.search_fn", key_fn=serve_signature,
                allowed=lambda key: key[0] in rungs and key[-1] < n_levels)
        self._queue: Optional[asyncio.Queue] = None
        self._dispatcher: Optional[asyncio.Task] = None
        self._watchdog_task: Optional[asyncio.Task] = None
        self._inflight: Optional[asyncio.Semaphore] = None
        self._fanout_tasks: set = set()
        self._pool = ThreadPoolExecutor(
            max_workers=max(1, cfg.max_inflight),
            thread_name_prefix="serve-compute",
        )
        self._closing = False
        self._closed = False
        # (B, Mq) shapes that have gone through the jit cache at least once
        self._warmed: set = set()
        # -- resilience (all None/no-op when cfg.resilience is None) --
        res = cfg.resilience
        self.fault_injector = FaultInjector()
        self._admission = AdmissionController(res) if res else None
        self._degrade = (DegradationController(len(self.search_fns), res)
                         if res else None)
        # items dequeued by the dispatcher but not yet handed to fan-out;
        # the watchdog fails these with DispatcherFailed on restart.
        # Loop-confined (only the event loop touches it) — no lock.
        self._claimed: Dict[_Item, float] = {}
        self._beat = 0.0  # dispatcher heartbeat (loop.time())
        # -- stats (threading lock: read from facade threads, written from
        # fan-out tasks). stats() reads the tracer's records that end after
        # `_since_ns` (the last reset_stats), and its counters --
        self._lock = threading.Lock()
        self._since_ns = 0
        self._level_served: Dict[int, int] = {}
        # control, not tracing: the degradation controller's recent
        # latencies, fed from the same fan-out stamp as `serve.request`
        self._recent_lat: collections.deque = collections.deque(maxlen=256)

    # -- lifecycle ----------------------------------------------------------

    async def start(self) -> None:
        """Idempotent: bind to the running loop and start the dispatcher."""
        if self._closed:
            raise ServerClosed("server already closed")
        if self._queue is None:
            loop = asyncio.get_running_loop()
            self._queue = asyncio.Queue()
            self._inflight = asyncio.Semaphore(max(1, self.cfg.max_inflight))
            self._beat = loop.time()
            self._dispatcher = loop.create_task(self._dispatch())
            if self.cfg.resilience is not None:
                self._watchdog_task = loop.create_task(self._watchdog())

    async def aclose(self) -> None:
        """Stop serving. In-flight batches complete and deliver results;
        still-queued requests get a terminal `ServerClosed` error."""
        if self._closed:
            return
        self._closing = True
        if self._watchdog_task is not None:
            self._watchdog_task.cancel()
            await asyncio.gather(self._watchdog_task, return_exceptions=True)
            self._watchdog_task = None
        if self._queue is not None:
            await self._queue.put(_STOP)
            # never let a dispatcher crash skip the drain below
            await asyncio.gather(self._dispatcher, return_exceptions=True)
            while True:
                try:
                    item = self._queue.get_nowait()
                except asyncio.QueueEmpty:
                    break
                if item is not _STOP and not item.future.done():
                    item.future.set_exception(
                        ServerClosed("server closed before request ran")
                    )
        if self._fanout_tasks:
            await asyncio.gather(
                *list(self._fanout_tasks), return_exceptions=True
            )
        # a dispatcher that died mid-claim leaves orphans; never strand them
        self._fail_claimed(ServerClosed("server closed before request ran"))
        self._pool.shutdown(wait=True)
        self._closed = True

    # -- client API ---------------------------------------------------------

    async def _enqueue(self, q_emb, q_mask, q_sal, *, _t_enqueue=None,
                       deadline_ms=None, slo="interactive") -> _Item:
        """Admission + enqueue; returns the queued `_Item` so callers (the
        sync facade) can cancel its future on their own timeout."""
        if self._closing or self._closed:
            raise ServerClosed("server is closed")
        await self.start()
        if self._admission is not None:
            reason = self._admission.admit(slo, self._queue.qsize())
            if reason is not None:
                raise Overloaded(reason)
        res = self.cfg.resilience
        t_ns = (time.perf_counter_ns() if _t_enqueue is None
                else int(_t_enqueue * 1e9))
        if deadline_ms is None and res is not None \
                and res.default_deadline_ms > 0:
            deadline_ms = res.default_deadline_ms
        deadline = (None if deadline_ms is None
                    else t_ns / 1e9 + deadline_ms / 1e3)
        fut = asyncio.get_running_loop().create_future()
        item = _Item(
            # client inputs are host arrays by contract — no device sync
            np.asarray(q_emb), np.asarray(q_mask), np.asarray(q_sal), fut,  # noqa: JAX05
            t_ns, self.tracer.new_id(), deadline, slo,
        )
        await self._queue.put(item)
        return item

    async def query(self, q_emb, q_mask, q_sal, *, _t_enqueue=None,
                    deadline_ms=None, slo="interactive"):
        """Awaitable single-query search; returns (scores (k,), ids (k,)).

        Raises `Overloaded` when admission sheds the request,
        `DeadlineExceeded` when ``deadline_ms`` (or the configured
        default) passes before results are ready. The result is a
        `Served` tuple carrying ``.level``.
        """
        item = await self._enqueue(
            q_emb, q_mask, q_sal, _t_enqueue=_t_enqueue,
            deadline_ms=deadline_ms, slo=slo,
        )
        try:
            return await item.future
        except asyncio.CancelledError:
            # caller abandoned the wait: kill the queued item too so it
            # stops occupying a batch slot
            if not item.future.done():
                item.future.cancel()
            raise

    def rung_for(self, n: int) -> int:
        """Smallest ladder rung that fits a batch of n requests."""
        for b in self.ladder:
            if b >= n:
                return b
        return self.ladder[-1]

    def warm_shapes(self, q_emb, q_mask, q_sal, rungs=None,
                    levels=None) -> None:
        """Pre-compile ladder rungs for one query geometry (blocking).

        Takes a single example query (Mq, D); tiles it to each rung and runs
        the jitted search once so serving never pays a compile stall. All
        degradation levels are warmed by default — stepping down the
        quality ladder under overload must never stall on a compile.
        """
        q = np.asarray(q_emb)
        qm = np.asarray(q_mask)
        qs = np.asarray(q_sal)
        if levels is None:
            levels = range(len(self.search_fns))
        for b in rungs if rungs is not None else self.ladder:
            qb = jnp.asarray(np.broadcast_to(q, (b,) + q.shape))
            qmb = jnp.asarray(np.broadcast_to(qm, (b,) + qm.shape))
            qsb = jnp.asarray(np.broadcast_to(qs, (b,) + qs.shape))
            for level in levels:
                out = self._call_search(level, qb, qmb, qsb)
                jax.block_until_ready(out)
            self._warmed.add((b, q.shape[0]))

    @property
    def compiled_shapes(self) -> set:
        """(B, Mq) pairs that have hit the jit compile cache."""
        return set(self._warmed)

    @property
    def search_fn(self) -> Callable:
        """The level-0 (full quality) search function."""
        return self.search_fns[0]

    @search_fn.setter
    def search_fn(self, fn: Callable) -> None:
        self.search_fns[0] = fn

    def _call_search(self, level: int, q, qm, qs):
        if self.recompile_sentry is not None:
            return self.recompile_sentry(q, qm, qs, level)
        return self.search_fns[level](q, qm, qs)

    def swap_search_fn(self, search_fn: Callable,
                       degraded_fns: Optional[Sequence[Callable]] = None,
                       ) -> None:
        """Atomically swap the underlying search function (live index
        mutation). The recompile sentry — and its signature history — stays
        in place: the serving ladder's compiled rung set is a property of
        the *server*, and a swapped-in function must keep honouring it.
        Batches already staged finish on whichever function they read.

        When the server carries degradation levels, pass matching
        ``degraded_fns`` built from the same new state — the level count
        is fixed at construction (it sizes the degradation controller).
        """
        if degraded_fns is not None:
            if len(degraded_fns) + 1 != len(self.search_fns):
                raise ValueError(
                    f"got {len(degraded_fns)} degraded fns for a server "
                    f"with {len(self.search_fns) - 1} degraded levels"
                )
            self.search_fns[1:] = list(degraded_fns)
        self.search_fns[0] = search_fn

    # -- dispatcher ---------------------------------------------------------

    def _resolve_exc(self, item: _Item, exc: BaseException) -> None:
        self._claimed.pop(item, None)
        if not item.future.done():
            item.future.set_exception(exc)

    def _fail_claimed(self, exc: BaseException) -> None:
        for it in list(self._claimed):
            self._resolve_exc(it, exc)

    def _drop_stale(self, item: _Item) -> bool:
        """Drop cancelled/expired items before they occupy a batch slot."""
        if item.future.done():
            # client cancelled (sync facade timeout / abandoned await)
            self._claimed.pop(item, None)
            return True
        if item.deadline is not None \
                and time.perf_counter() >= item.deadline:
            self.tracer.count("serve.deadline_expired")
            self._resolve_exc(item, DeadlineExceeded(
                "deadline passed while queued — dropped before staging"))
            return True
        return False

    def _observe_level(self) -> int:
        """One degradation-controller observation per coalesced batch."""
        if self._degrade is None:
            return 0
        res = self.cfg.resilience
        depth_frac = self._queue.qsize() / max(1, res.max_queue)
        with self._lock:
            recent = list(self._recent_lat)
        p99 = float(np.percentile(np.asarray(recent), 99)) if recent else 0.0
        return self._degrade.observe(depth_frac, p99)

    async def _dispatch(self) -> None:
        loop = asyncio.get_running_loop()
        tracer = self.tracer
        while True:
            self._beat = loop.time()
            item = await self._queue.get()
            self._beat = loop.time()
            if item is _STOP:
                return
            # taken off the queue: its `serve.queue` span ends here (fan-out
            # records it, off the coalescing path)
            t_first = item.t_claim_ns = time.perf_counter_ns()
            self._claimed[item] = t_first / 1e9
            self.fault_injector.fire("dispatch")
            if self._closing:
                self._resolve_exc(item, ServerClosed(
                    "server closed before request ran"))
                continue
            if self._drop_stale(item):
                continue
            batch = [item]
            stop_after = False
            deadline = loop.time() + self.cfg.max_wait_ms / 1e3
            while len(batch) < self.cfg.max_batch:
                rem = deadline - loop.time()
                if rem <= 0:
                    break
                try:
                    nxt = await asyncio.wait_for(self._queue.get(), rem)
                except asyncio.TimeoutError:
                    break
                if nxt is _STOP:
                    stop_after = True
                    break
                nxt.t_claim_ns = time.perf_counter_ns()
                self._claimed[nxt] = nxt.t_claim_ns / 1e9
                if not self._drop_stale(nxt):
                    batch.append(nxt)
            # deadlines/cancellations may have landed while coalescing
            batch = [r for r in batch if not self._drop_stale(r)]
            if not batch:
                if stop_after:
                    return
                continue
            batch_id = tracer.new_id()
            t_coalesced = time.perf_counter_ns()
            tracer.mark("serve.coalesce", t_first, t_coalesced,
                        parent=batch_id, batch=batch_id)
            level = self._observe_level()
            # bound in-flight batches (double buffer): once a slot frees we
            # stage the next batch here while the previous one still computes
            await self._inflight.acquire()
            tracer.mark("serve.slot", t_coalesced, time.perf_counter_ns(),
                        parent=batch_id, batch=batch_id)
            # the wait for a slot can be long under load: re-check for
            # cancellations/deadlines that landed during it
            batch = [r for r in batch if not self._drop_stale(r)]
            if not batch:
                self._inflight.release()
                if stop_after:
                    return
                continue
            try:
                with tracer.span("serve.stage", parent=batch_id,
                                 batch=batch_id, rung=self.rung_for(
                                     len(batch))):
                    staged = self._stage(batch, level)
            except Exception as e:  # noqa: BLE001 - e.g. mixed-shape batch
                # fail this batch but keep the dispatcher alive: a staging
                # error (say, two coalesced queries with different Mq) must
                # not strand every later request on a dead queue
                self._inflight.release()
                for r in batch:
                    self._resolve_exc(r, e)
                if stop_after:
                    return
                continue
            if qmaxsim_k.traced_group(staged[0]) > 1:
                # this rung's ADC scan scores its queries in groups, one
                # one-hot per (block, patch) a group
                tracer.count("serve.onehot_shared_queries", len(batch))
            for r in batch:
                # handed to fan-out, which owns resolution from here; the
                # watchdog only covers the dequeue->stage window
                self._claimed.pop(r, None)
            task = loop.create_task(self._fanout(
                batch, level, *staged, batch_id=batch_id, t_first=t_first))
            self._fanout_tasks.add(task)
            task.add_done_callback(self._fanout_tasks.discard)
            if stop_after:
                return

    async def _watchdog(self) -> None:
        """Detect a dead or hung dispatcher, restart it, and fail the
        requests it had claimed with `DispatcherFailed` instead of letting
        them hang. Runs only when `ServeConfig.resilience` is set."""
        res = self.cfg.resilience
        loop = asyncio.get_running_loop()
        while not (self._closing or self._closed):
            await asyncio.sleep(res.watchdog_interval_s)
            if self._closing or self._closed:
                return
            d = self._dispatcher
            if d is None:
                continue
            if d.done():
                err = None if d.cancelled() else d.exception()
                logger.error("serve dispatcher died (%r); restarting", err)
                self._restart_dispatcher(loop, DispatcherFailed(
                    f"dispatcher died ({err!r}) while this request was "
                    "claimed; restarted by watchdog"))
                continue
            pending = bool(self._claimed) or self._queue.qsize() > 0
            if pending and (loop.time() - self._beat) > res.stall_timeout_s:
                logger.error(
                    "serve dispatcher hung (heartbeat %.1fs stale, "
                    "%d claimed, depth %d); restarting",
                    loop.time() - self._beat, len(self._claimed),
                    self._queue.qsize())
                d.cancel()
                await asyncio.gather(d, return_exceptions=True)
                self._restart_dispatcher(loop, DispatcherFailed(
                    "dispatcher hung past stall_timeout_s while this "
                    "request was claimed; restarted by watchdog"))

    def _restart_dispatcher(self, loop, exc: DispatcherFailed) -> None:
        self._fail_claimed(exc)
        self.tracer.count("serve.watchdog_restarts")
        self._beat = loop.time()
        self._dispatcher = loop.create_task(self._dispatch())

    def _stage(self, batch: List[_Item], level: int = 0):
        """Host staging: pad to the ladder rung and start the host->device
        transfer. Runs on the event loop, overlapped with the previous
        batch's device compute."""
        self.fault_injector.fire("stage")
        rung = self.rung_for(len(batch))
        first = batch[0]
        q = np.zeros((rung,) + first.q_emb.shape, first.q_emb.dtype)
        qm = np.zeros((rung,) + first.q_mask.shape, bool)
        qs = np.zeros((rung,) + first.q_sal.shape, first.q_sal.dtype)
        for i, r in enumerate(batch):
            q[i], qm[i], qs[i] = r.q_emb, r.q_mask, r.q_sal
        self._warmed.add((rung, first.q_emb.shape[0]))
        return rung, jnp.asarray(q), jnp.asarray(qm), jnp.asarray(qs)

    async def _fanout(self, batch: List[_Item], level: int, rung: int,
                      q, qm, qs, *, batch_id: int, t_first: int) -> None:
        loop = asyncio.get_running_loop()
        tracer = self.tracer

        def _compute():
            self.fault_injector.fire("compute")
            with tracer.span("serve.compute", parent=batch_id,
                             batch=batch_id, rung=rung):
                out = self._call_search(level, q, qm, qs)
                jax.block_until_ready(out)  # only blocking point, off loop
            # device->host transfer stays on the executor thread too: done
            # on the event loop it head-of-line blocked every coalesced
            # request behind one D2H copy (JAX05)
            with tracer.span("serve.d2h", parent=batch_id, batch=batch_id):
                return np.asarray(out[0]), np.asarray(out[1])

        try:
            work = loop.run_in_executor(self._pool, _compute)
            # each request's wait in the queue, recorded while the batch
            # computes: off the coalescing and staging path
            for r in batch:
                tracer.mark("serve.queue", r.t_enqueue_ns, r.t_claim_ns,
                            parent=r.span_id, request=r.span_id)
            scores, ids = await work
            self.fault_injector.fire("fanout")
        except Exception as e:  # noqa: BLE001 - forwarded to every waiter
            for r in batch:
                self._resolve_exc(r, e)
            self._inflight.release()
            return
        with tracer.span("serve.fanout", parent=batch_id, batch=batch_id):
            now_ns = time.perf_counter_ns()
            now = now_ns / 1e9
            for r in batch:
                tracer.mark("serve.request", r.t_enqueue_ns, now_ns,
                            span_id=r.span_id, request=r.span_id,
                            batch=batch_id)
            with self._lock:
                self._recent_lat.extend(
                    (now_ns - r.t_enqueue_ns) / 1e6 for r in batch)
            for i, r in enumerate(batch):
                if r.deadline is not None and now >= r.deadline:
                    # result arrived, but nobody is waiting for it anymore
                    tracer.count("serve.deadline_expired")
                    self._resolve_exc(r, DeadlineExceeded(
                        "deadline passed during compute"))
                    continue
                if not r.future.done():
                    r.future.set_result(Served((scores[i], ids[i]), level))
                    with self._lock:
                        self._level_served[level] = (
                            self._level_served.get(level, 0) + 1
                        )
        tracer.mark("serve.batch", t_first, time.perf_counter_ns(),
                    span_id=batch_id, batch=batch_id, rung=rung,
                    requests=tuple(r.span_id for r in batch))
        self._inflight.release()

    # -- stats --------------------------------------------------------------

    def _resilience_stats(self) -> Dict[str, Any]:
        """Caller holds self._lock. The timeout counter is unconditional
        (sync-facade timeouts cancel their queued item on any server); the
        overload/degradation counters only exist on a guarded server."""
        counters = self.tracer.counters
        out: Dict[str, Any] = {"timeouts": counters.get("serve.timeouts", 0)}
        if self.cfg.resilience is None:
            return out
        shed = (self._admission.stats() if self._admission is not None
                else {"interactive": 0, "batch": 0})
        out.update({
            "deadline_expired": counters.get("serve.deadline_expired", 0),
            "shed": sum(shed.values()),
            "shed_interactive": shed["interactive"],
            "shed_batch": shed["batch"],
            "degrade_level": (self._degrade.level
                              if self._degrade is not None else 0),
            "level_served": dict(self._level_served),
            "watchdog_restarts": counters.get("serve.watchdog_restarts", 0),
        })
        return out

    def _window(self):
        """The `serve.request` and `serve.batch` records of the stats
        window: those that ended after the last `reset_stats`."""
        with self._lock:
            since = self._since_ns
        recs = self.tracer.records(since_ns=since)
        return ([s for s in recs if s.name == "serve.request"],
                [s for s in recs if s.name == "serve.batch"])

    @property
    def latencies_ms(self) -> List[float]:
        """Enqueue-to-answer latency of each request answered in the stats
        window, in the order they were answered."""
        return [s.ms for s in self._window()[0]]

    @property
    def batch_sizes(self) -> List[int]:
        """Real requests of each batch run in the stats window."""
        return [len(s.ids["requests"]) for s in self._window()[1]]

    def stats(self) -> Dict[str, Any]:
        """Latency, batch and rung figures of the requests and batches
        whose records end in the window (the ring keeps the last
        `repro.tracing.CAPACITY` records); resilience counts since the
        last reset_stats."""
        reqs, batches = self._window()
        per_rung: Dict[int, List[int]] = {}
        for s in batches:
            c = per_rung.setdefault(s.ids["rung"], [0, 0])
            c[0] += 1
            c[1] += len(s.ids["requests"])
        rungs = {b: {"batches": n_b, "occupancy": rows / (n_b * b)}
                 for b, (n_b, rows) in sorted(per_rung.items())}
        with self._lock:
            res = self._resilience_stats()
        if not reqs:
            # no traffic yet: report zeros, never fabricated percentiles
            return {"n": 0, "p50_ms": 0.0, "p99_ms": 0.0, "mean_batch": 0.0,
                    "qps": 0.0, "rungs": {}, **res}
        lat = np.array([s.ms for s in reqs])
        # qps over the wall-clock window, first enqueue -> last answer,
        # never from summed overlapping per-request latencies
        t0 = min(s.start_ns for s in reqs)
        t1 = max(s.end_ns for s in reqs)
        return {
            "n": int(lat.size),
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99)),
            "mean_batch": (float(np.mean([len(s.ids["requests"])
                                          for s in batches]))
                           if batches else 0.0),
            "qps": lat.size / max((t1 - t0) / 1e9, 1e-9),
            "rungs": rungs,
            **res,
        }

    def recompile_report(self) -> Optional[Dict[str, Any]]:
        """The recompile sentry's signature report (None when the guard
        is off — see ServeConfig.guard_recompiles)."""
        if self.recompile_sentry is None:
            return None
        return self.recompile_sentry.report()

    def reset_stats(self) -> None:
        """Start a new stats window (e.g. after a warmup/compile request,
        which would otherwise skew qps): stats() reads only records that
        end from now on. The tracer's ring keeps its records. Resilience
        counters reset too, except watchdog_restarts (lifetime health),
        and so does serve.onehot_shared_queries."""
        with self._lock:
            self._since_ns = time.perf_counter_ns()
            self._level_served = {}
            self._recent_lat.clear()
        self.tracer.drop_counters("serve.timeouts", "serve.deadline_expired",
                                  "serve.onehot_shared_queries")
        if self._admission is not None:
            self._admission.reset()


class _Request:
    """v1 request handle: wait on ``event``, read ``result`` / ``error``."""

    __slots__ = ("q_emb", "q_mask", "q_sal", "event", "result", "error",
                 "t_enqueue", "deadline_ms", "slo", "item", "abandoned")

    def __init__(self, q_emb, q_mask, q_sal, deadline_ms=None,
                 slo="interactive"):
        self.q_emb, self.q_mask, self.q_sal = q_emb, q_mask, q_sal
        self.event = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None
        self.t_enqueue = time.perf_counter()
        self.deadline_ms = deadline_ms
        self.slo = slo
        self.item: Optional[_Item] = None   # set once enqueued (loop thread)
        self.abandoned = False              # set by the facade's timeout


class RetrievalServer:
    """Sync facade over `AsyncRetrievalServer` (thread-backed event loop).

    Keeps the v1 surface — ``submit`` -> waitable request, blocking
    ``query`` — so existing call sites work unchanged while the serving
    core is asyncio."""

    def __init__(self, search_fn: Callable, cfg: ServeConfig,
                 degraded_fns: Sequence[Callable] = ()):
        self.search_fn = search_fn
        self.cfg = cfg
        self._async = AsyncRetrievalServer(search_fn, cfg, degraded_fns)
        self._loop = asyncio.new_event_loop()
        self._thread = threading.Thread(
            target=self._loop.run_forever, name="serve-loop", daemon=True
        )
        self._thread.start()
        self._run(self._async.start()).result(timeout=10.0)
        self._closed = False
        # serialises submit-vs-close: a submit never schedules onto a loop
        # that close() has already begun stopping
        self._lifecycle = threading.Lock()

    def _run(self, coro):
        return asyncio.run_coroutine_threadsafe(coro, self._loop)

    # -- v1 surface ---------------------------------------------------------

    def submit(self, q_emb, q_mask, q_sal, *, deadline_ms=None,
               slo="interactive") -> _Request:
        req = _Request(np.asarray(q_emb), np.asarray(q_mask),
                       np.asarray(q_sal), deadline_ms, slo)

        async def _go():
            try:
                item = await self._async._enqueue(
                    req.q_emb, req.q_mask, req.q_sal,
                    _t_enqueue=req.t_enqueue,
                    deadline_ms=req.deadline_ms, slo=req.slo,
                )
                req.item = item
                if req.abandoned and not item.future.done():
                    item.future.cancel()
                req.result = await item.future
            except BaseException as e:  # noqa: BLE001 - handed to waiter
                req.error = e
            finally:
                req.event.set()

        with self._lifecycle:
            if self._closed:
                req.error = ServerClosed("server is closed")
                req.event.set()
                return req
            try:
                self._run(_go())
            except RuntimeError as e:   # loop torn down concurrently
                req.error = ServerClosed(f"server is closed ({e})")
                req.event.set()
        return req

    def cancel(self, req: _Request) -> None:
        """Cancel a submitted request from any thread: its queued item is
        killed on the loop (freeing the batch slot) and the abandonment is
        counted in ``stats()["timeouts"]``."""
        def _cancel():
            req.abandoned = True
            if req.item is not None and not req.item.future.done():
                req.item.future.cancel()
            self._async.tracer.count("serve.timeouts")

        try:
            self._loop.call_soon_threadsafe(_cancel)
        except RuntimeError:
            pass  # loop already closed: nothing left to cancel

    def query(self, q_emb, q_mask, q_sal, timeout: float = 30.0, *,
              deadline_ms=None, slo="interactive"):
        req = self.submit(q_emb, q_mask, q_sal, deadline_ms=deadline_ms,
                          slo=slo)
        if not req.event.wait(timeout):
            # cancel the queued item — pre-fix it stayed enqueued and
            # occupied a batch slot long after this client gave up
            self.cancel(req)
            raise TimeoutError("retrieval request timed out")
        if req.error is not None:
            raise req.error
        return req.result

    def warm_shapes(self, q_emb, q_mask, q_sal, rungs=None,
                    levels=None) -> None:
        self._async.warm_shapes(q_emb, q_mask, q_sal, rungs, levels)

    def swap_search_fn(self, search_fn: Callable,
                       degraded_fns: Optional[Sequence[Callable]] = None,
                       ) -> None:
        self._async.swap_search_fn(search_fn, degraded_fns)

    @property
    def ladder(self) -> Tuple[int, ...]:
        return self._async.ladder

    @property
    def tracer(self) -> Tracer:
        return self._async.tracer

    @property
    def latencies_ms(self) -> List[float]:
        return self._async.latencies_ms

    @property
    def batch_sizes(self) -> List[int]:
        return self._async.batch_sizes

    def stats(self) -> Dict[str, Any]:
        return self._async.stats()

    @property
    def recompile_sentry(self):
        return self._async.recompile_sentry

    @property
    def fault_injector(self) -> FaultInjector:
        return self._async.fault_injector

    def recompile_report(self) -> Optional[Dict[str, Any]]:
        return self._async.recompile_report()

    def reset_stats(self) -> None:
        self._async.reset_stats()

    def close(self):
        """Drain and stop: in-flight batches deliver results, queued
        requests get a terminal `ServerClosed` error (no 30 s timeouts).
        Raises RuntimeError if the serving loop thread fails to join —
        a silent leak of a live thread is never reported as success."""
        with self._lifecycle:
            if self._closed:
                return
            self._closed = True
        try:
            self._run(self._async.aclose()).result(timeout=30.0)
        finally:
            self._loop.call_soon_threadsafe(self._loop.stop)
            self._thread.join(timeout=5.0)
            if self._thread.is_alive():
                state = (f"thread={self._thread.name!r} alive=True "
                         f"daemon={self._thread.daemon} "
                         f"loop_running={self._loop.is_running()}")
                logger.error("serving loop failed to join within 5 s (%s)",
                             state)
                raise RuntimeError(
                    f"serving loop thread failed to join within 5 s ({state})"
                )
            self._loop.close()
