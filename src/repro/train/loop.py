"""Fault-tolerant training loop + GPipe pipeline parallelism.

Fault tolerance (docs/design.md §4):
  * checkpoint/restart — CheckpointManager (atomic+async), auto-resume from
    the latest committed step;
  * NaN/inf guard — the *jitted* step rejects non-finite updates
    functionally (params/opt_state roll back to their pre-step values and a
    skip counter increments), so a single bad batch or flaky-core bitflip
    never corrupts the run;
  * straggler mitigation — data-layer (PrefetchPipeline timeout reserve),
    plus a per-step wall-clock watchdog that logs steps exceeding
    `straggler_factor` x the trailing-median step time (at real scale this
    signal feeds the scheduler to evict the slow host);
  * preemption simulation is tested in tests/test_train_loop.py by killing
    the loop mid-run and resuming.

Pipeline parallelism: `make_pipelined_fn` implements GPipe microbatch
rotation with shard_map + ppermute over a "pipe" mesh axis — used for
depth-sharding beyond the (data, model) production mesh; validated against
the sequential reference in tests on a host-device mesh.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.ckpt.checkpoint import CheckpointManager

PyTree = Any


@dataclasses.dataclass
class LoopConfig:
    total_steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: str = "/tmp/repro_ckpt"
    keep_ckpts: int = 3
    log_every: int = 10
    straggler_factor: float = 3.0


def guard_nonfinite(step_fn: Callable) -> Callable:
    """Wrap (params, opt_state, batch) -> (params, opt_state, metrics) with
    a functional non-finite rollback. Adds metrics["skipped"]."""

    def guarded(params, opt_state, batch):
        new_p, new_o, metrics = step_fn(params, opt_state, batch)
        ok = jnp.isfinite(metrics["loss"])
        sel = lambda a, b: jax.tree.map(
            lambda x, y: jnp.where(ok, x, y), a, b)
        params = sel(new_p, params)
        opt_state = sel(new_o, opt_state)
        metrics = dict(metrics)
        metrics["skipped"] = jnp.where(ok, 0, 1).astype(jnp.int32)
        return params, opt_state, metrics

    return guarded


def run(step_fn: Callable, params: PyTree, opt_state: PyTree,
        batches: Iterator[Dict[str, Any]], cfg: LoopConfig,
        start_step: int = 0, manager: Optional[CheckpointManager] = None,
        log_fn: Callable[[str], None] = print) -> Dict[str, Any]:
    """Run the guarded training loop. step_fn must already be jitted.

    Returns {params, opt_state, step, history, stats}.
    """
    if manager is None:
        manager = CheckpointManager(cfg.ckpt_dir, keep=cfg.keep_ckpts)

    # auto-resume
    restored = manager.restore_latest((params, opt_state))
    if restored is not None:
        start_step, (params, opt_state) = restored
        log_fn(f"[loop] resumed from step {start_step}")

    history = []
    step_times = []
    n_skipped = 0
    stats = {"stragglers": 0, "skipped": 0}
    step = start_step
    guarded = guard_nonfinite(step_fn)

    for step in range(start_step, cfg.total_steps):
        batch = next(batches)
        t0 = time.perf_counter()
        params, opt_state, metrics = guarded(params, opt_state, batch)
        loss = float(metrics["loss"])      # sync point
        dt = time.perf_counter() - t0
        step_times.append(dt)
        n_skipped += int(metrics["skipped"])
        if len(step_times) > 10:
            med = float(np.median(step_times[-50:]))
            if dt > cfg.straggler_factor * med:
                stats["stragglers"] += 1
                log_fn(f"[loop] straggler step {step}: {dt:.3f}s "
                       f"(median {med:.3f}s)")
        history.append({"step": step, "loss": loss,
                        **{k: float(v) for k, v in metrics.items()
                           if k not in ("loss",)}})
        if cfg.log_every and step % cfg.log_every == 0:
            log_fn(f"[loop] step {step} loss {loss:.4f} ({dt*1e3:.1f} ms)")
        if cfg.ckpt_every and (step + 1) % cfg.ckpt_every == 0:
            manager.save_async(step + 1, (params, opt_state))

    manager.wait()
    manager.save(cfg.total_steps, (params, opt_state))
    stats["skipped"] = n_skipped
    return {"params": params, "opt_state": opt_state, "step": step + 1,
            "history": history, "stats": stats}


# ---------------------------------------------------------------------------
# GPipe pipeline parallelism (shard_map + ppermute microbatch rotation)
# ---------------------------------------------------------------------------

def make_pipelined_fn(mesh: Mesh, stage_fn: Callable, n_microbatches: int,
                      axis: str = "pipe") -> Callable:
    """Build f(stage_params, x) running `stage_fn` depth-sharded over `axis`.

    stage_params: pytree with leading dim = n_stages (sharded over axis).
    x: (n_microbatches * mb, ...) activations entering stage 0.
    Schedule: standard GPipe fill/flush — T = n_micro + n_stages - 1 ticks;
    at each tick every stage processes the microbatch it holds (if valid)
    then ppermutes its output to the next stage. Bubble fraction
    (n_stages-1)/T as usual.
    """
    n_stages = mesh.shape[axis]

    def pipelined(stage_params, x):
        def local(stage_params, x):
            # stage_params leaves have leading dim 1 (this stage's slice)
            sp = jax.tree.map(lambda a: a[0], stage_params)
            stage = jax.lax.axis_index(axis)
            mb = x.shape[0] // n_microbatches
            mbs = x.reshape(n_microbatches, mb, *x.shape[1:])
            out = jnp.zeros_like(mbs)
            # current activation buffer + validity tag (mb index, -1 invalid)
            buf = jnp.zeros((mb, *x.shape[1:]), x.dtype)
            tag = jnp.int32(-1)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

            def tick(carry, t):
                buf, tag, out = carry
                # stage 0 injects microbatch t (if any remain)
                inject = jnp.logical_and(stage == 0, t < n_microbatches)
                safe_t = jnp.minimum(t, n_microbatches - 1)
                buf = jnp.where(inject, mbs[safe_t], buf)
                tag = jnp.where(inject, safe_t, tag)
                # all stages process their buffer (compute is unconditional;
                # invalid buffers produce garbage that is never committed)
                y = stage_fn(sp, buf)
                # last stage commits finished microbatches
                commit = jnp.logical_and(stage == n_stages - 1, tag >= 0)
                safe_tag = jnp.maximum(tag, 0)
                out = jnp.where(
                    commit,
                    jax.lax.dynamic_update_index_in_dim(out, y, safe_tag, 0),
                    out)
                # rotate activations to the next stage
                buf = jax.lax.ppermute(y, axis, perm)
                tag = jax.lax.ppermute(tag, axis, perm)
                # stage 0 receives from the last stage: clear its tag
                tag = jnp.where(stage == 0, -1, tag)
                return (buf, tag, out), None

            (buf, tag, out), _ = jax.lax.scan(
                tick, (buf, tag, out), jnp.arange(n_stages + n_microbatches - 1))
            # only the last stage holds real outputs; broadcast via psum
            out = jnp.where(stage == n_stages - 1, out, 0)
            out = jax.lax.psum(out, axis)
            return out.reshape(x.shape)

        spec_params = jax.tree.map(lambda _: P(axis), stage_params)
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(spec_params, P()), out_specs=P(),
            check_vma=False)(stage_params, x)

    return pipelined
