"""Fault-tolerant serving: deadlines, shedding, degradation, chaos.

Controller unit tests run unmarked; the fault-injection / watchdog /
crash-persistence suite is marked ``chaos`` (network-free, < 60 s) and
runs standalone in CI's analysis job via ``pytest -m chaos``.
"""
import asyncio
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.serving.resilience import (AdmissionController, DeadlineExceeded,
                                      DegradationController,
                                      DispatcherFailed, FaultInjected,
                                      FaultInjector, Overloaded,
                                      ResilienceConfig, TokenBucket)
from repro.serving.server import (AsyncRetrievalServer, RetrievalServer,
                                  ServeConfig, Served)

chaos = pytest.mark.chaos

Q = (np.zeros((4, 16), np.float32), np.ones(4, bool),
     np.zeros(4, np.float32))


def _fake_search(q, qm, qs):
    b = q.shape[0]
    return (np.zeros((b, 5), np.float32),
            np.tile(np.arange(5, dtype=np.int64), (b, 1)))


def _fake_degraded(q, qm, qs):
    b = q.shape[0]
    return (np.full((b, 5), -1.0, np.float32),
            np.tile(np.arange(5, dtype=np.int64), (b, 1)))


def _poll(predicate, timeout=5.0, msg="condition"):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < timeout:
        if predicate():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


# ---------------------------------------------------------------------------
# Controller units
# ---------------------------------------------------------------------------

def test_token_bucket_rate_and_burst():
    tb = TokenBucket(rate=10.0, burst=2.0)
    assert tb.try_take(now=0.0) and tb.try_take(now=0.0)
    assert not tb.try_take(now=0.0)           # burst exhausted
    assert tb.try_take(now=0.1)               # 0.1 s * 10/s = 1 token back
    assert not tb.try_take(now=0.1)
    unlimited = TokenBucket(rate=0.0, burst=1.0)
    assert all(unlimited.try_take(now=0.0) for _ in range(100))


def test_admission_queue_bound_and_batch_sheds_first():
    cfg = ResilienceConfig(max_queue=10, shed_batch_frac=0.5)
    adm = AdmissionController(cfg)
    assert adm.admit("interactive", depth=0) is None
    assert adm.admit("batch", depth=0) is None
    # batch sheds at half depth, interactive only at the hard bound
    assert adm.admit("batch", depth=5) is not None
    assert adm.admit("interactive", depth=5) is None
    assert "queue full" in adm.admit("interactive", depth=10)
    counts = adm.stats()
    assert counts == {"interactive": 1, "batch": 1}
    adm.reset()
    assert adm.stats() == {"interactive": 0, "batch": 0}
    with pytest.raises(ValueError, match="unknown SLO class"):
        adm.admit("bulk", depth=0)


def test_admission_token_bucket_per_class():
    cfg = ResilienceConfig(max_queue=100, interactive_rate=1.0,
                           interactive_burst=2.0)
    adm = AdmissionController(cfg)
    t = 100.0
    assert adm.admit("interactive", 0, now=t) is None
    assert adm.admit("interactive", 0, now=t) is None
    assert "token bucket" in adm.admit("interactive", 0, now=t)
    # batch class has its own (unlimited) bucket
    assert adm.admit("batch", 0, now=t) is None


def test_degradation_hysteresis():
    cfg = ResilienceConfig(degrade_high_frac=0.75, degrade_low_frac=0.25,
                           degrade_hold=3)
    dc = DegradationController(n_levels=3, cfg=cfg)
    assert dc.observe(0.1) == 0               # calm at level 0: stays
    assert dc.observe(0.8) == 1               # hot: step down immediately
    assert dc.observe(0.9) == 2
    assert dc.observe(0.9) == 2               # clamped at n_levels - 1
    assert dc.observe(0.5) == 2               # hysteresis band: hold
    assert dc.observe(0.1) == 2               # calm 1/3
    assert dc.observe(0.1) == 2               # calm 2/3
    assert dc.observe(0.5) == 2               # band resets the calm run
    assert dc.observe(0.1) == 2
    assert dc.observe(0.1) == 2
    assert dc.observe(0.1) == 1               # calm 3/3: step back up
    assert len(dc.transitions) == 3
    # p99 trigger is an independent OR condition
    cfg2 = ResilienceConfig(degrade_p99_ms=50.0)
    dc2 = DegradationController(n_levels=2, cfg=cfg2)
    assert dc2.observe(0.0, p99_ms=80.0) == 1


def test_fault_injector_arm_fire_clear():
    fi = FaultInjector()
    fi.fire("stage")                          # unarmed: no-op
    fi.arm("stage", times=2)
    with pytest.raises(FaultInjected):
        fi.fire("stage")
    with pytest.raises(FaultInjected):
        fi.fire("stage")
    fi.fire("stage")                          # exhausted
    assert fi.fired["stage"] == 2
    fi.arm("compute", latency_s=0.05)
    t0 = time.perf_counter()
    fi.fire("compute")                        # latency only, no exception
    assert time.perf_counter() - t0 >= 0.05
    fi.arm("fanout", exc=RuntimeError("boom"))
    fi.clear("fanout")
    fi.fire("fanout")                         # cleared: no-op


# ---------------------------------------------------------------------------
# Satellite regressions: sync timeout leak, close() join, qps span
# ---------------------------------------------------------------------------

def test_sync_timeout_cancels_queued_item():
    """Pre-fix: a timed-out sync query stayed queued and occupied a batch
    slot. Now it is cancelled on the loop and counted in stats."""
    gate = threading.Event()

    def stalled_search(q, qm, qs):
        gate.wait(10.0)
        return _fake_search(q, qm, qs)

    server = RetrievalServer(
        stalled_search, ServeConfig(max_batch=1, max_wait_ms=0.5,
                                    max_inflight=1))
    try:
        # A occupies the single compute slot; B times out while queued
        req_a = server.submit(*Q)
        with pytest.raises(TimeoutError, match="timed out"):
            server.query(*Q, timeout=0.3)
        gate.set()
        assert req_a.event.wait(5.0) and req_a.error is None
        # B's cancelled item must be pruned, not staged: only A (and the
        # post-fix probe) ever reach compute
        s, ids = server.query(*Q, timeout=5.0)
        assert s.shape == (5,)
        _poll(lambda: server.stats()["timeouts"] == 1, msg="timeout count")
        assert server.stats()["n"] == 2       # A + probe, never B
    finally:
        gate.set()
        server.close()


def test_close_raises_when_thread_fails_to_join():
    server = RetrievalServer(_fake_search, ServeConfig(max_batch=1))
    real_thread = server._thread

    class StuckThread:
        name = "serve-loop"
        daemon = True

        def join(self, timeout=None):
            pass

        def is_alive(self):
            return True

    server._thread = StuckThread()
    with pytest.raises(RuntimeError, match="failed to join"):
        server.close()
    # the real loop did stop; finish teardown manually
    real_thread.join(timeout=5.0)
    assert not real_thread.is_alive()
    server._loop.close()


def test_qps_span_from_timestamps_only():
    """qps must come from the monotonic first-enqueue -> last-answer
    window of the requests' own `serve.request` records — never the old
    sum-of-overlapping-latencies fallback, which inflated qps by orders
    of magnitude under concurrency. The window is read from the records
    themselves, so there is no separate timestamp that a reset_stats
    race could leave missing."""
    server = RetrievalServer(_fake_search,
                             ServeConfig(max_batch=4, max_wait_ms=1.0))
    try:
        for _ in range(4):
            server.query(*Q, timeout=5.0)
        st = server.stats()
        assert st["n"] == 4 and st["qps"] > 0.0
        span = st["n"] / st["qps"]
        assert span <= 60.0                   # sane wall-clock window
        reqs = server.tracer.records("serve.request")
        assert len(reqs) == 4
        window_s = (max(s.end_ns for s in reqs)
                    - min(s.start_ns for s in reqs)) / 1e9
        assert st["qps"] == pytest.approx(4 / window_s)
        assert st["p50_ms"] == pytest.approx(
            float(np.percentile([s.ms for s in reqs], 50)))
    finally:
        server.close()


def test_reset_stats_race_restores_window():
    """reset_stats while a batch is in flight: the fan-out backfills the
    window from the batch's own enqueue times, so qps stays derived from
    real timestamps."""
    gate = threading.Event()

    def slow_search(q, qm, qs):
        gate.wait(5.0)
        return _fake_search(q, qm, qs)

    server = RetrievalServer(slow_search,
                             ServeConfig(max_batch=1, max_wait_ms=0.2))
    try:
        req = server.submit(*Q)
        time.sleep(0.05)                      # batch now inside search_fn
        server.reset_stats()
        gate.set()
        assert req.event.wait(5.0) and req.error is None
        st = server.stats()
        assert st["n"] == 1
        assert 0.0 < st["qps"] < float("inf")
    finally:
        gate.set()
        server.close()


# ---------------------------------------------------------------------------
# Deadlines, shedding, degradation (async integration)
# ---------------------------------------------------------------------------

def test_deadline_expired_before_staging():
    async def go():
        srv = AsyncRetrievalServer(
            _fake_search,
            ServeConfig(max_batch=2, max_wait_ms=0.5,
                        resilience=ResilienceConfig()))
        # stall the dispatcher between dequeue and staging: the deadline
        # passes while the request is claimed, so it is dropped before
        # any compute happens
        srv.fault_injector.arm("dispatch", latency_s=0.08)
        with pytest.raises(DeadlineExceeded, match="before staging"):
            await srv.query(*Q, deadline_ms=20.0)
        st = srv.stats()
        assert st["deadline_expired"] == 1
        assert st["n"] == 0                   # never staged, never computed
        # deadline generous enough: served normally, tagged level 0
        out = await srv.query(*Q, deadline_ms=5000.0)
        assert isinstance(out, Served) and out.level == 0
        await srv.aclose()

    asyncio.run(go())


def test_deadline_expired_during_compute():
    async def go():
        srv = AsyncRetrievalServer(
            _fake_search,
            ServeConfig(max_batch=1, max_wait_ms=0.2,
                        resilience=ResilienceConfig()))
        srv.fault_injector.arm("compute", latency_s=0.08)
        with pytest.raises(DeadlineExceeded, match="during compute"):
            await srv.query(*Q, deadline_ms=20.0)
        assert srv.stats()["deadline_expired"] == 1
        await srv.aclose()

    asyncio.run(go())


def test_overload_sheds_with_explicit_rejection():
    gate = threading.Event()

    def stalled(q, qm, qs):
        gate.wait(10.0)
        return _fake_search(q, qm, qs)

    async def go():
        srv = AsyncRetrievalServer(
            stalled,
            ServeConfig(max_batch=1, max_wait_ms=0.2, max_inflight=1,
                        resilience=ResilienceConfig(max_queue=4,
                                                    shed_batch_frac=0.5)))
        tasks = [asyncio.ensure_future(srv.query(*Q)) for _ in range(12)]
        await asyncio.sleep(0.1)
        batch_rej = None
        try:
            await srv.query(*Q, slo="batch")  # queue deep: batch class shed
        except Overloaded as e:
            batch_rej = str(e)
        gate.set()
        outs = await asyncio.gather(*tasks, return_exceptions=True)
        st = srv.stats()
        await srv.aclose()
        return outs, st, batch_rej

    outs, st, batch_rej = asyncio.run(go())
    gate.set()
    shed = [o for o in outs if isinstance(o, Overloaded)]
    served = [o for o in outs if isinstance(o, Served)]
    assert len(shed) + len(served) == 12      # every request resolved
    assert len(shed) >= 1 and len(served) >= 1
    assert st["shed"] == len(shed) + 1        # + the explicit batch probe
    assert batch_rej is not None and "batch class shed" in batch_rej


def test_degradation_ladder_serves_and_recovers():
    async def go():
        res = ResilienceConfig(max_queue=64, degrade_high_frac=0.05,
                               degrade_low_frac=0.01, degrade_hold=2,
                               watchdog_interval_s=0.02)
        srv = AsyncRetrievalServer(
            _fake_search,
            ServeConfig(max_batch=2, max_wait_ms=0.2, max_inflight=1,
                        resilience=res),
            degraded_fns=(_fake_degraded,))
        srv.fault_injector.arm("compute", latency_s=0.01, times=1000)
        burst = await asyncio.gather(*[srv.query(*Q) for _ in range(40)],
                                     return_exceptions=True)
        st_hot = srv.stats()
        srv.fault_injector.clear()
        # trickle: calm observations step the ladder back to level 0
        for _ in range(30):
            out = await srv.query(*Q)
            if out.level == 0 and srv.stats()["degrade_level"] == 0:
                break
            await asyncio.sleep(0.02)
        st_calm = srv.stats()
        await srv.aclose()
        return burst, st_hot, st_calm

    burst, st_hot, st_calm = asyncio.run(go())
    served = [o for o in burst if isinstance(o, Served)]
    assert len(served) == 40                  # nothing hung, nothing lost
    # the burst pushed the controller past level 0 and level-1 responses
    # went out tagged (and came from the degraded function: scores -1)
    degraded = [o for o in served if o.level == 1]
    assert degraded and st_hot["level_served"].get(1, 0) == len(degraded)
    assert all(np.all(np.asarray(o[0]) == -1.0) for o in degraded)
    assert st_calm["degrade_level"] == 0      # recovered after the burst


# ---------------------------------------------------------------------------
# Chaos: fault injection at each site, watchdog, crash-safe persistence
# ---------------------------------------------------------------------------

@chaos
def test_chaos_stage_fault_isolated_sentry_unchanged():
    """An injected host-staging failure fails exactly its own batch; the
    dispatcher survives, later queries succeed, and the recompile
    sentry's signature set is untouched (satellite: staging isolation
    under FaultInjector)."""
    async def go():
        srv = AsyncRetrievalServer(
            _fake_search,
            ServeConfig(max_batch=2, max_wait_ms=0.5, guard_recompiles=True,
                        resilience=ResilienceConfig()))
        srv.warm_shapes(*Q)
        sigs_before = set(srv.recompile_sentry.signatures)
        srv.fault_injector.arm("stage")
        with pytest.raises(FaultInjected):
            await srv.query(*Q)
        assert srv.stats()["watchdog_restarts"] == 0  # dispatcher survived
        out = await srv.query(*Q)
        assert isinstance(out, Served)
        assert set(srv.recompile_sentry.signatures) == sigs_before
        await srv.aclose()

    asyncio.run(go())


@chaos
@pytest.mark.parametrize("site", ["compute", "fanout"])
def test_chaos_compute_and_fanout_faults_contained(site):
    async def go():
        srv = AsyncRetrievalServer(
            _fake_search,
            ServeConfig(max_batch=2, max_wait_ms=0.5,
                        resilience=ResilienceConfig()))
        srv.fault_injector.arm(site)
        with pytest.raises(FaultInjected):
            await srv.query(*Q)
        out = await srv.query(*Q)             # server fully functional
        assert isinstance(out, Served)
        assert srv.stats()["watchdog_restarts"] == 0
        await srv.aclose()

    asyncio.run(go())


@chaos
def test_chaos_dispatcher_death_watchdog_restarts():
    """A fault at the dispatch site kills the coalescing loop itself. The
    watchdog restarts it and fails the claimed request with a terminal
    DispatcherFailed instead of letting it hang."""
    async def go():
        srv = AsyncRetrievalServer(
            _fake_search,
            ServeConfig(max_batch=2, max_wait_ms=0.5,
                        resilience=ResilienceConfig(
                            watchdog_interval_s=0.02)))
        srv.fault_injector.arm("dispatch")
        with pytest.raises(DispatcherFailed, match="restarted by watchdog"):
            await srv.query(*Q)
        out = await srv.query(*Q)             # restarted loop serves again
        assert isinstance(out, Served)
        assert srv.stats()["watchdog_restarts"] == 1
        await srv.aclose()

    asyncio.run(go())


@chaos
def test_chaos_dispatcher_hang_watchdog_restarts():
    """A dispatcher stuck past stall_timeout_s with claimed work is
    cancelled and restarted; its claimed request gets DispatcherFailed."""
    gate = threading.Event()

    def stalled(q, qm, qs):
        gate.wait(10.0)
        return _fake_search(q, qm, qs)

    async def go():
        srv = AsyncRetrievalServer(
            stalled,
            ServeConfig(max_batch=1, max_wait_ms=0.2, max_inflight=1,
                        resilience=ResilienceConfig(
                            watchdog_interval_s=0.05,
                            stall_timeout_s=0.3)))
        # A occupies the only compute slot; B gets claimed and the
        # dispatcher blocks acquiring an in-flight slot -> heartbeat stale
        task_a = asyncio.ensure_future(srv.query(*Q))
        await asyncio.sleep(0.05)
        task_b = asyncio.ensure_future(srv.query(*Q))
        with pytest.raises(DispatcherFailed, match="hung"):
            await task_b
        gate.set()
        out_a = await task_a                  # in-flight batch still lands
        assert isinstance(out_a, Served)
        assert srv.stats()["watchdog_restarts"] >= 1
        out = await srv.query(*Q)
        assert isinstance(out, Served)
        await srv.aclose()

    try:
        asyncio.run(go())
    finally:
        gate.set()


@chaos
def test_chaos_guarded_degraded_serving_stays_on_ladder():
    """Degraded levels are part of the sentry's declared signature set:
    a full warm + overload burst compiles exactly ladder x levels and
    nothing else (no off-ladder recompiles while shedding/degrading)."""
    async def go():
        res = ResilienceConfig(max_queue=64, degrade_high_frac=0.05,
                               degrade_low_frac=0.01, degrade_hold=2)
        srv = AsyncRetrievalServer(
            _fake_search,
            ServeConfig(max_batch=4, max_wait_ms=0.2, max_inflight=1,
                        guard_recompiles=True, resilience=res),
            degraded_fns=(_fake_degraded,))
        srv.warm_shapes(*Q)                   # warms every level x rung
        srv.fault_injector.arm("compute", latency_s=0.01, times=1000)
        outs = await asyncio.gather(*[srv.query(*Q) for _ in range(30)],
                                    return_exceptions=True)
        await srv.aclose()
        return srv, outs

    srv, outs = asyncio.run(go())
    assert all(isinstance(o, Served) for o in outs)
    assert {o.level for o in outs} >= {1}     # degraded serving happened
    sigs = set(srv.recompile_sentry.signatures)
    assert {s[0] for s in sigs} == set(srv.ladder)
    assert {s[-1] for s in sigs} == {0, 1}
    # exact closed set: every (rung, level) pair, nothing else
    assert len(sigs) == len(srv.ladder) * 2
    srv.recompile_sentry.check_cache_consistent()


@chaos
def test_chaos_sigkill_mid_save_leaves_loadable_index(tmp_path):
    """SIGKILL a process mid-`IndexBackend.save`: the index path must
    hold the previous complete version (atomic rename) and load clean —
    never a torn file."""
    path = str(tmp_path / "idx.npz")
    code = f"""
import numpy as np, jax.numpy as jnp
from repro.core import index as index_mod
from repro.retrieval.base import RetrieverState, get_backend
rng = np.random.default_rng(0)
emb = rng.normal(size=(256, 8, 16)).astype(np.float32)
mask = np.ones((256, 8), bool)
ff = index_mod.build_float_flat(jnp.asarray(emb), jnp.asarray(mask))
state = RetrieverState(codebook=jnp.zeros((4, 16), jnp.float32),
                       backend_state=ff,
                       rerank_codes=jnp.zeros((256, 8), jnp.uint8),
                       rerank_mask=jnp.asarray(mask))
b = get_backend("float_flat")
i = 0
while True:
    b.save({path!r}, state)
    i += 1
    print("SAVED", i, flush=True)
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..",
                                      "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.Popen([sys.executable, "-c", code], env=env,
                            stdout=subprocess.PIPE, text=True)
    try:
        # wait for at least one committed save, then kill mid-loop
        line = proc.stdout.readline()
        assert line.startswith("SAVED"), line
        for _ in range(3):
            proc.stdout.readline()
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    from repro.retrieval.base import get_backend
    state = get_backend("float_flat").load(path)   # previous complete save
    assert state.rerank_codes.shape == (256, 8)


@chaos
def test_chaos_corrupt_index_fails_with_named_array(tmp_path):
    import jax.numpy as jnp
    from repro.core import index as index_mod
    from repro.retrieval.base import RetrieverState, get_backend
    emb = np.random.default_rng(0).normal(size=(32, 4, 8)).astype(
        np.float32)
    mask = np.ones((32, 4), bool)
    ff = index_mod.build_float_flat(jnp.asarray(emb), jnp.asarray(mask))
    state = RetrieverState(codebook=jnp.zeros((4, 8), jnp.float32),
                           backend_state=ff,
                           rerank_codes=jnp.zeros((32, 4), jnp.uint8),
                           rerank_mask=jnp.asarray(mask))
    backend = get_backend("float_flat")
    path = backend.save(str(tmp_path / "idx"), state)
    # flip bits in one leaf but keep the stored checksums: load must name
    # the corrupt array, never return silently-bad data
    with np.load(path) as z:
        payload = {k: z[k] for k in z.files}
    bad = payload["leaf_0001"].copy()
    bad.flat[0] += 1
    payload["leaf_0001"] = bad
    np.savez(path, **payload)
    with pytest.raises(ValueError, match="leaf_0001"):
        backend.load(path)
    # a v2-style file (no checksums key) still loads: nothing to verify
    del payload["checksums"]
    payload["leaf_0001"] = bad
    payload["format_version"] = np.asarray(2, np.int64)
    np.savez(path, **payload)
    backend.load(path)


@chaos
def test_chaos_corrupt_checkpoint_fails_with_named_leaf(tmp_path):
    from repro.ckpt import checkpoint as ckpt
    tree = {"w": np.arange(16, dtype=np.float32).reshape(4, 4),
            "b": np.ones((4,), np.float32)}
    path = ckpt.save(str(tmp_path), 1, tree)
    restored = ckpt.restore(path, tree)
    np.testing.assert_array_equal(np.asarray(restored["w"]), tree["w"])
    npz_path = os.path.join(path, "arrays.npz")
    with np.load(npz_path) as z:
        arrays = {k: z[k] for k in z.files}
    key = sorted(arrays)[0]
    arrays[key] = arrays[key] + 1             # corrupt one leaf on disk
    np.savez(npz_path, **arrays)
    with pytest.raises(ValueError, match="checksum mismatch on leaf"):
        ckpt.restore(path, tree)


@chaos
def test_chaos_sigkill_mid_checkpoint_previous_step_restores(tmp_path):
    from repro.ckpt import checkpoint as ckpt
    code = f"""
import numpy as np
from repro.ckpt import checkpoint as ckpt
tree = {{"w": np.zeros((256, 256), np.float32)}}
step = 0
while True:
    step += 1
    ckpt.save({str(tmp_path)!r}, step, tree)
    print("STEP", step, flush=True)
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..",
                                      "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.Popen([sys.executable, "-c", code], env=env,
                            stdout=subprocess.PIPE, text=True)
    try:
        assert proc.stdout.readline().startswith("STEP")
        proc.stdout.readline()
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    step = ckpt.latest_step(str(tmp_path))
    assert step is not None                   # some step fully committed
    tree = {"w": np.zeros((256, 256), np.float32)}
    restored = ckpt.restore(
        os.path.join(str(tmp_path), f"step_{step:08d}"), tree)
    assert np.asarray(restored["w"]).shape == (256, 256)


@chaos
def test_chaos_overload_drill_every_request_resolves():
    """Mini overload drill (the full curve runs in benchmarks/latency.py):
    a 4x-ish burst with deadlines sheds/serves/expires every request —
    zero hung — and the ladder recovers to level 0 afterwards."""
    async def go():
        res = ResilienceConfig(max_queue=16, shed_batch_frac=0.5,
                               degrade_high_frac=0.25,
                               degrade_low_frac=0.05, degrade_hold=2,
                               default_deadline_ms=2000.0,
                               watchdog_interval_s=0.02)
        srv = AsyncRetrievalServer(
            _fake_search,
            ServeConfig(max_batch=4, max_wait_ms=0.2, max_inflight=1,
                        resilience=res),
            degraded_fns=(_fake_degraded,))
        srv.fault_injector.arm("compute", latency_s=0.02, times=10_000)
        tasks = []
        for _ in range(120):
            tasks.append(asyncio.ensure_future(srv.query(*Q)))
            await asyncio.sleep(0.0005)       # ~4x the sustainable rate
        outs = await asyncio.gather(*tasks, return_exceptions=True)
        srv.fault_injector.clear()
        level = None
        for _ in range(50):
            out = await srv.query(*Q, deadline_ms=5000.0)
            level = srv.stats()["degrade_level"]
            if out.level == 0 and level == 0:
                break
            await asyncio.sleep(0.02)
        st = srv.stats()
        await srv.aclose()
        return outs, st, level

    outs, st, level = asyncio.run(go())
    served = [o for o in outs if isinstance(o, Served)]
    shed = [o for o in outs if isinstance(o, Overloaded)]
    expired = [o for o in outs if isinstance(o, DeadlineExceeded)]
    assert len(served) + len(shed) + len(expired) == 120  # zero hung
    assert served and shed                    # overload actually shed
    assert level == 0                         # recovered post-burst
    assert st["watchdog_restarts"] == 0
