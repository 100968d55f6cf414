"""Train-step builder + fault-tolerant runner.

``build_train_step`` returns one jitted function, ``train_step``:
    state, metrics = step_fn(state, batch)
with gradient accumulation (microbatching via lax.scan), mixed precision
(params fp32, compute bf16 per model config), NaN guarding, and — when a
DP-compression method is selected — per-shard grads reduced through
``compressed_psum`` under shard_map.

``run`` is the production loop: checkpoint every k steps (async, atomic),
auto-resume (incl. onto a different mesh = elastic), NaN → restore + skip
batch, straggler monitor (step-time EWMA), bounded restarts on exceptions.
When ``reslice_fn`` is given, ``straggler_patience`` consecutive flagged
steps trigger an elastic re-slice: the loop flushes a checkpoint, hands
control to ``reslice_fn(state, step)`` (``repro.train.elastic`` builds the
degraded mesh, re-resolves the sharding specs, restores onto it, re-jits),
and continues at the same global step on the surviving devices.

Profiler names: the optimizer's update runs under ``jax.named_scope
("optimizer")``; each step of ``run`` opens three host spans
(``jax.profiler.TraceAnnotation``): ``train.h2d`` (the batch and its copy
to the device), ``train.run`` (the step's dispatch) and ``train.sync``
(the wait for the step's loss).
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.dist import api as dist
from repro.train import checkpoint as ckpt_lib
from repro.train.compression import compressed_psum
from repro.train.optimizer import Optimizer

log = logging.getLogger(__name__)


@dataclasses.dataclass
class TrainConfig:
    grad_accum: int = 1
    checkpoint_every: int = 100
    keep_last: int = 3
    max_restarts: int = 3
    log_every: int = 10
    grad_compression: str = "none"       # none | bf16 | int8
    straggler_factor: float = 3.0        # step > f × EWMA ⇒ flagged
    straggler_patience: int = 3          # consecutive flags ⇒ re-slice
    #   (only with a reslice_fn; the EWMA skips warm-up steps — first step
    #   after a (re)compile/restore and the step after a checkpoint save —
    #   so compile and ckpt I/O never masquerade as stragglers)


def build_train_step(loss_fn: Callable, optimizer: Optimizer,
                     cfg: TrainConfig,
                     project: Optional[Callable] = None) -> Callable:
    """loss_fn(params, batch) -> (loss, metrics dict of scalars).

    ``project`` (optional): applied to params after every optimizer update —
    the quantized-substrate requantization hook (a backend whose stored
    parameters are not what the math sees folds the float update back in;
    see ``EmbeddingBackend.project`` / ``repro.models.recsys.
    make_project_fn``).  ``allow_int=True`` on the grad calls lets integer
    leaves (int8 codes) flow through with float0 cotangents; the float0-
    aware guards below and the optimizer's frozen-leaf wrapper keep them
    out of the arithmetic.
    """

    def grads_of(params, batch):
        if cfg.grad_accum > 1:
            def micro(carry, mb):
                (l, g) = jax.value_and_grad(
                    lambda p: loss_fn(p, mb)[0], allow_int=True)(params)
                # float0 cotangents (integer leaves) never enter the f32
                # accumulator — they stay float0 on the way out via the
                # same dtype test the optimizer freeze uses
                acc = jax.tree.map(
                    lambda a, gg: a if gg.dtype == jax.dtypes.float0
                    else jnp.add(a, gg), carry[1], g)
                return (carry[0] + l, acc), None
            zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                params)
            mbs = jax.tree.map(
                lambda x: x.reshape((cfg.grad_accum,
                                     x.shape[0] // cfg.grad_accum)
                                    + x.shape[1:]), batch)
            (loss, grads), _ = jax.lax.scan(micro, (0.0, zero), mbs)
            inv = 1.0 / cfg.grad_accum
            return loss * inv, jax.tree.map(lambda g: g * inv, grads)
        loss, grads = jax.value_and_grad(lambda p: loss_fn(p, batch)[0],
                                         allow_int=True)(params)
        return loss, grads

    def train_step(state, batch):
        params, opt_state, step = state["params"], state["opt"], state["step"]
        if cfg.grad_compression != "none":
            ctx = dist.current()
            assert ctx is not None, "compression needs a mesh"
            from jax.sharding import PartitionSpec as P
            dp = ctx.dp_axes

            def body(p, mb, res):
                # res leaves carry a leading per-DP-shard axis of size 1 here
                res = jax.tree.map(lambda r: r[0], res)
                loss, g = grads_of(p, mb)
                loss = jax.lax.pmean(loss, dp)
                g, res = compressed_psum(g, res, dp, cfg.grad_compression)
                res = jax.tree.map(lambda r: r[None], res)
                return loss, g, res

            pspec = jax.tree.map(lambda _: P(), params)
            bspec = jax.tree.map(lambda _: P(dp), batch)
            efspec = jax.tree.map(lambda _: P(dp), state["ef"])
            loss, grads, ef = jax.shard_map(
                body, mesh=ctx.mesh,
                in_specs=(pspec, bspec, efspec),
                out_specs=(P(), pspec, efspec),
                check_vma=False)(params, batch, state["ef"])
            state = dict(state, ef=ef)
        else:
            loss, grads = grads_of(params, batch)

        with jax.named_scope("optimizer"):
            # NaN guard: skip the update if any grad is non-finite (float0
            # cotangents carry no values to inspect)
            finite = jnp.isfinite(loss)
            for g in jax.tree.leaves(grads):
                if g.dtype == jax.dtypes.float0:
                    continue
                finite &= jnp.all(jnp.isfinite(g))
            new_params, new_opt = optimizer.update(params, grads, opt_state,
                                                   step)
            params = jax.tree.map(
                lambda new, old: jnp.where(finite, new, old), new_params,
                params)
            opt_state = jax.tree.map(
                lambda new, old: jnp.where(finite, new, old), new_opt,
                opt_state)
            if project is not None:
                # requantization fold (ALPT): idempotent on a skipped
                # update — a between-steps state projects to itself
                params = project(params)
        state = dict(state, params=params, opt=opt_state, step=step + 1)
        return state, {"loss": loss, "finite": finite.astype(jnp.float32)}

    return jax.jit(train_step, donate_argnums=(0,))


def init_state(params, optimizer: Optimizer, cfg: TrainConfig) -> dict:
    state = {"params": params, "opt": optimizer.init(params),
             "step": jnp.zeros((), jnp.int32)}
    if cfg.grad_compression != "none":
        ctx = dist.current()
        n_dp = 1
        if ctx is not None:
            for a in ctx.dp_axes:
                n_dp *= ctx.mesh.shape[a]
        # error-feedback residual: one fp32 copy per DP shard (leading axis
        # sharded over dp — per-device it is a single model-sized buffer)
        state["ef"] = jax.tree.map(
            lambda p: jnp.zeros((n_dp,) + p.shape, jnp.float32), params)
    return state


def _live_shardings(state):
    """The state's own resident shardings, for restoring a checkpoint back
    onto the CURRENT layout — after an elastic re-slice the mesh mid-run is
    the degraded one, and a NaN/exception restore must not replicate a
    model-sharded table onto every survivor.  Leaves without a sharding
    (host numpy from an earlier restore) map to None = default placement.
    """
    return jax.tree.map(
        lambda x: getattr(x, "sharding", None), state)


@dataclasses.dataclass
class RunReport:
    steps_done: int
    final_loss: float
    restarts: int
    nan_events: int
    straggler_steps: int
    losses: list
    state: dict = None       # final train state (donation-safe handle)
    reslices: int = 0        # elastic mesh rebuilds (reslice_fn calls)


def run(state, step_fn: Callable, batch_at: Callable[[int], dict],
        n_steps: int, cfg: TrainConfig,
        ckpt_dir: Optional[str] = None,
        inject_fault_at: Optional[int] = None,
        reslice_fn: Optional[Callable] = None,
        timer: Callable[[], float] = time.monotonic) -> RunReport:
    """Fault-tolerant training loop (single-controller).

    ``batch_at(step)`` must be a pure function of step (resume correctness).
    ``inject_fault_at``: raise a simulated node failure at that step once
    (legacy test hook; ``repro.train.elastic.FaultPlan`` is the general
    harness).
    ``reslice_fn(state, step) -> (state, step_fn)``: elastic re-slice hook,
    called after ``cfg.straggler_patience`` consecutive straggler-flagged
    steps with a just-flushed checkpoint on disk — it must hand back state
    and a step function resident on the rebuilt (degraded) mesh; the loop
    resumes counting the same global step.  ``None`` (default) keeps the
    monitor passive: stragglers are only counted.
    ``timer``: monotonic clock used for step timing — injectable so fault
    drills (``FaultPlan``) drive the straggler EWMA deterministically.
    """
    saver = ckpt_lib.AsyncCheckpointer(ckpt_dir, cfg.keep_last) \
        if ckpt_dir else None
    restarts = 0
    nan_events = 0
    straggler_steps = 0
    straggler_run = 0        # consecutive flags since the last quiet step
    reslices = 0
    ewma = None
    warmup = True            # next measured dt is compile / restore / ckpt
    #   I/O — excluded from both the EWMA and the straggler flag
    losses: list = []
    injected = {"done": False}

    start = int(jax.device_get(state["step"]))
    if ckpt_dir:
        restored = ckpt_lib.restore_latest(ckpt_dir, state)
        if restored is not None:
            state, manifest = restored
            start = int(manifest["step"])

    step = start
    while step < n_steps:
        try:
            if inject_fault_at is not None and step == inject_fault_at \
                    and not injected["done"]:
                injected["done"] = True
                raise RuntimeError("injected node failure")
            t0 = timer()
            with TraceAnnotation("train.h2d"):
                batch = {k: jnp.asarray(v)
                         for k, v in batch_at(step).items()}
            with TraceAnnotation("train.run"):
                state, metrics = step_fn(state, batch)
            with TraceAnnotation("train.sync"):
                loss = float(jax.device_get(metrics["loss"]))
            dt = timer() - t0
            if warmup:
                warmup = False
            else:
                if ewma is not None and dt > cfg.straggler_factor * ewma:
                    straggler_steps += 1
                    straggler_run += 1
                else:
                    straggler_run = 0
                ewma = dt if ewma is None else 0.9 * ewma + 0.1 * dt
            saved_this_step = False
            if not np.isfinite(loss):
                nan_events += 1
                if ckpt_dir:
                    if saver:
                        saver.wait()    # never race the in-flight write
                    restored = ckpt_lib.restore_latest(
                        ckpt_dir, state, shardings=_live_shardings(state))
                    if restored is not None:
                        state, manifest = restored
                warmup = True           # restore I/O pollutes the next dt
                step += 1               # skip the poisoned batch; fall
                #   through: a pending re-slice must still fire (slow AND
                #   corrupting hardware is one failure, not two)
            else:
                losses.append(loss)
                step += 1
                if saver and step % cfg.checkpoint_every == 0:
                    saver.save(step, state)
                    saved_this_step = True
                    warmup = True           # ckpt I/O pollutes the next dt
            if reslice_fn is not None \
                    and straggler_run >= cfg.straggler_patience:
                # reset the monitor FIRST: if the rebuild itself fails
                # (caught below as a restart) it must take another
                # `patience` flagged steps to re-trigger, not retry on
                # every following step
                straggler_run = 0
                ewma = None             # new hardware, new step-time prior
                warmup = True
                # flush the current state so the rebuild restores exactly
                # this global step onto the degraded mesh (skip only when
                # the boundary save above already snapshotted this step —
                # a NaN trigger step never saved, modulo or not)
                if saver:
                    if not saved_this_step:
                        saver.save(step, state)
                    saver.wait()
                # contract: reslice_fn hands back state/step_fn resident
                # on the rebuilt mesh AT this global step (it restores the
                # checkpoint just flushed) — the loop keeps counting from
                # here, monotonically
                state, step_fn = reslice_fn(state, step)
                reslices += 1
        except Exception:
            restarts += 1
            if restarts > cfg.max_restarts:
                raise
            # a restart must never hide its cause
            log.warning("train step %d failed; restart %d of %d", step,
                        restarts, cfg.max_restarts, exc_info=True)
            if ckpt_dir:
                if saver:
                    try:
                        saver.wait()    # never race the in-flight write
                    except Exception:   # NOT KeyboardInterrupt/SystemExit
                        pass            # failed save = missing snapshot;
                        #   restore falls back to the previous one
                restored = ckpt_lib.restore_latest(
                    ckpt_dir, state, shardings=_live_shardings(state))
                if restored is not None:
                    state, manifest = restored
                    step = int(manifest["step"])
            warmup = True
            # the rewind replays steps: stale consecutive-flag counts and
            # the old timing prior must not leak across the restart
            straggler_run = 0
            ewma = None
            continue
    if saver:
        try:
            saver.save(step, state)
            saver.wait()
        except Exception:               # NOT KeyboardInterrupt/SystemExit
            # same tolerance the in-loop paths apply to failed saves: the
            # previous atomic snapshot is still valid, and a completed
            # run's report + final state matter more than the last write
            pass
    return RunReport(steps_done=step - start,
                     final_loss=losses[-1] if losses else float("nan"),
                     restarts=restarts, nan_events=nan_events,
                     straggler_steps=straggler_steps, losses=losses,
                     state=state, reslices=reslices)
