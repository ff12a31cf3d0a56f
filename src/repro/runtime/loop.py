"""Fault-tolerant training loop: checkpoint/restart, straggler watchdog,
elastic re-mesh on device loss.

The loop owns generic train *state* (a pytree) and a *program*:

    program.init_state(mesh)            -> state
    program.make_step(mesh)             -> step_fn(state, batch) -> (state, metrics)
    program.state_sharding(mesh)        -> key -> Sharding   (for restore)

Recovery policy (DESIGN.md §5):

* every ``ckpt_every`` steps the state is snapshotted asynchronously
  (atomic on disk; the data cursor rides in the manifest);
* a failed step (device loss, hang, XLA runtime error) triggers:
  1. drop the poisoned jit executable & mesh,
  2. re-form the largest healthy mesh (``elastic_mesh``),
  3. restore the last checkpoint *resharded* onto the new mesh,
  4. replay the data stream from the restored cursor (deterministic
     pipeline => exactly-once semantics for optimizer updates),
* after ``max_failures`` consecutive failures the loop re-raises —
  at that point the job-level scheduler owns recovery.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Callable, Dict, Optional, Protocol

import jax

from repro.checkpoint import CheckpointManager, latest_step, restore
from repro.data import DataCursor, SyntheticTokens, make_global_batch
from repro.obs import NULL as NULL_OBS, Observability, TapBuffer
from repro.runtime.watchdog import StepDeadlineExceeded, StepWatchdog

log = logging.getLogger("repro.runtime")


class Program(Protocol):
    """Optional hooks (duck-typed, used when present): ``flush_async
    (state) -> state`` barriers in-flight background work into the state
    before a checkpoint; ``reset_async()`` drops it on recovery."""

    def init_state(self, mesh) -> Any: ...

    def make_step(self, mesh) -> Callable: ...

    def state_sharding(self, mesh) -> Callable: ...


@dataclasses.dataclass
class LoopConfig:
    total_steps: int
    ckpt_dir: str
    ckpt_every: int = 50
    keep: int = 3
    max_failures: int = 3
    model_parallel: int = 1
    # pipeline (stage) degree: > 1 re-meshes onto (stage, data, model)
    # and is preserved across elastic recoveries like model_parallel
    # (the stage partition is baked into layouts and schedules)
    pipeline_parallel: int = 1
    log_every: int = 10
    straggler_factor: float = 2.0
    hard_deadline_s: Optional[float] = None


class TrainLoop:
    def __init__(
        self,
        cfg: LoopConfig,
        program: Program,
        dataset: SyntheticTokens,
        *,
        mesh_fn: Optional[Callable[..., Any]] = None,
        inject: Optional[Callable[[int], None]] = None,
        obs: Optional[Observability] = None,
    ):
        """``inject(step)`` is the fault-drill hook: tests/examples raise
        DeviceLoss/StepDeadlineExceeded from it to exercise recovery."""
        from repro.runtime.elastic import elastic_mesh

        self.cfg = cfg
        self.program = program
        self.dataset = dataset
        self.obs = obs if obs is not None else NULL_OBS
        self.mesh_fn = mesh_fn or (
            lambda exclude=0: elastic_mesh(cfg.model_parallel,
                                           pp=cfg.pipeline_parallel,
                                           exclude=exclude,
                                           obs=self.obs))
        self.inject = inject
        self.ckpt = CheckpointManager(cfg.ckpt_dir, keep=cfg.keep)
        self.watchdog = StepWatchdog(
            straggler_factor=cfg.straggler_factor,
            hard_deadline_s=cfg.hard_deadline_s,
            obs=self.obs)
        self.metrics_history: list = []
        # fenced wall of every completed step, replays included
        self.step_walls: list = []
        self.n_recoveries = 0
        self._mesh_cm = None
        # device metrics buffered per step, drained in one batched
        # transfer per log_every window (repro.obs.taps)
        self._taps = TapBuffer()
        if self.obs.enabled:
            self._c_steps = self.obs.counter(
                "train_steps_total", "completed train steps")
            self._c_recov = self.obs.counter(
                "train_recoveries_total", "elastic checkpoint-restores")
            self._c_ckpt = self.obs.counter(
                "train_checkpoints_total", "async checkpoint snapshots")

    def _drain_taps(self):
        """One batched device_get for every buffered step; record ALL
        of them in the history (the old loop sampled at log_every).
        Returns the last drained row for formatting, or None."""
        rows = self._taps.drain()
        last = None
        for tag, m in rows:
            row = {"step": tag, **m}
            self.metrics_history.append(row)
            last = row
            if self.obs.enabled:
                self.obs.write({"kind": "train_step", **row})
                for k, v in m.items():
                    self.obs.gauge(f"train_{k}").set(v)
        return last

    # -- lifecycle ---------------------------------------------------------

    def _fresh(self, mesh):
        state = self.program.init_state(mesh)
        return state, DataCursor(0)

    def _restore(self, mesh):
        like = self.program.init_state(mesh)   # structure donor
        shard_of = self.program.state_sharding(mesh)
        state, manifest = restore(
            self.cfg.ckpt_dir, like,
            sharding_fn=lambda key, arr: shard_of(key))
        cursor = DataCursor.from_json(manifest["meta"]["cursor"])
        log.info("restored step %d onto %s", manifest["step"],
                 dict(mesh.shape))
        return state, cursor

    def _start(self, exclude: int = 0):
        mesh = self.mesh_fn(exclude=exclude)
        # expose the abstract mesh so model shard_hints are live inside
        # the jitted steps; re-entered on every (elastic) re-mesh
        if self._mesh_cm is not None:
            self._mesh_cm.__exit__(None, None, None)
        self._mesh_cm = jax.set_mesh(mesh)
        self._mesh_cm.__enter__()
        if latest_step(self.cfg.ckpt_dir) is not None:
            state, cursor = self._restore(mesh)
        else:
            state, cursor = self._fresh(mesh)
        step_fn = self.program.make_step(mesh)
        return mesh, state, cursor, step_fn

    # -- main --------------------------------------------------------------

    def run(self) -> Dict[str, Any]:
        try:
            return self._run()
        finally:
            # leave no mesh in scope, also when a step error propagates
            if self._mesh_cm is not None:
                self._mesh_cm.__exit__(None, None, None)
                self._mesh_cm = None

    def _run(self) -> Dict[str, Any]:
        failures = 0
        exclude = 0
        mesh, state, cursor, step_fn = self._start()
        t_start = time.monotonic()

        while cursor.step < self.cfg.total_steps:
            step = cursor.step
            try:
                if self.inject is not None:
                    self.inject(step)
                batch = make_global_batch(self.dataset, cursor, mesh)
                with self.watchdog.step(), \
                        self.obs.span("train_step",
                                      args={"step": step}):
                    state, metrics = step_fn(state, batch)
                    jax.block_until_ready(
                        jax.tree.leaves(metrics)[0])
            except Exception as e:  # noqa: BLE001
                if not _recoverable(e):
                    raise
                failures += 1
                self.n_recoveries += 1
                # buffered tap arrays may be poisoned by the device
                # loss: drop them unread (a device_get would re-raise)
                self._taps.clear()
                if self.obs.enabled:
                    self._c_recov.inc()
                    self.obs.event("recovery", step=step,
                                   error=type(e).__name__,
                                   lost=getattr(e, "lost", 0))
                log.warning("step %d failed (%s); recovery %d/%d",
                            step, type(e).__name__, failures,
                            self.cfg.max_failures)
                if failures > self.cfg.max_failures:
                    raise
                self.ckpt.wait()
                if latest_step(self.cfg.ckpt_dir) is None:
                    # nothing to restore: recovery re-inits from seed
                    # and replays from step 0 — loud, because repeated
                    # pre-first-checkpoint failures rework everything
                    # (each successful step resets the failure budget)
                    log.warning(
                        "recovery with no checkpoint: restarting from "
                        "fresh init, %d steps of progress replayed",
                        step)
                # async-refresh programs: drop any in-flight inverse
                # refresh — the restored factors no longer match it
                reset = getattr(self.program, "reset_async", None)
                if reset is not None:
                    reset()
                exclude += getattr(e, "lost", 0)
                mesh, state, cursor, step_fn = self._start(exclude)
                # fresh timing window: the first post-restore step
                # recompiles and must not trip the hang deadline.
                # Cumulative counters (n_steps / n_stragglers) survive —
                # replacing the watchdog here used to zero them, so the
                # final report undercounted stragglers after a recovery.
                self.watchdog.reset_window()
                continue

            failures = 0
            self.step_walls.append(self.watchdog.last_dt)
            cursor = cursor.advance()
            if self.obs.enabled:
                self._c_steps.inc()
            if self.watchdog.last_was_straggler:
                log.warning("straggler step %d (%d so far)", step,
                            self.watchdog.n_stragglers)
                if self.obs.enabled:
                    self.obs.event("straggler", step=step)
            # push device metrics without reading them (no sync);
            # drain the whole window in ONE batched device_get at the
            # log cadence — every step lands in metrics_history, only
            # the *formatting* happens at log_every
            self._taps.push(step, metrics)
            if step % self.cfg.log_every == 0:
                last = self._drain_taps()
                if last is not None:
                    log.info("step %d %s", last["step"],
                             {k: v for k, v in last.items()
                              if k != "step"})
            if cursor.step % self.cfg.ckpt_every == 0 \
                    or cursor.step == self.cfg.total_steps:
                # async-refresh programs: snapshot with the in-flight
                # inverse refresh folded in (so it isn't lost across a
                # restore) — but only the snapshot; rebinding the live
                # state here would make the training trajectory depend
                # on the checkpoint cadence
                flush = getattr(self.program, "flush_async", None)
                save_state = flush(state) if flush is not None \
                    else state
                with self.obs.span("ckpt_save_dispatch",
                                   args={"step": cursor.step}):
                    self.ckpt.save_async(
                        cursor.step, save_state,
                        meta={"cursor": cursor.to_json()})
                if self.obs.enabled:
                    self._c_ckpt.inc()

        self._drain_taps()   # tail of the last (partial) window
        self.ckpt.wait()
        return {
            "steps": cursor.step,
            "wall_s": time.monotonic() - t_start,
            "recoveries": self.n_recoveries,
            "stragglers": self.watchdog.n_stragglers,
            "step_wall_s": self.step_walls,
            "history": self.metrics_history,
        }


#: XLA runtime status markers that indicate a sick device / lost data
#: rather than a programming error (absl status codes as surfaced in
#: ``JaxRuntimeError`` messages).
_XLA_RECOVERABLE_MARKERS = (
    "RESOURCE_EXHAUSTED", "DATA_LOSS", "UNAVAILABLE", "ABORTED",
)


def _recoverable(e: BaseException) -> bool:
    """Only explicitly-known failure classes trigger checkpoint-restore.

    The old heuristic ("device" AND "error" anywhere in the message)
    classified ordinary programming errors as recoverable and silently
    looped checkpoint-restore over real bugs. Now: the repo's own fault
    types, or an XLA *runtime* error carrying a known sick-device status
    marker. Everything else re-raises to the caller."""
    from repro.runtime.elastic import DeviceLoss

    if isinstance(e, (DeviceLoss, StepDeadlineExceeded)):
        return True
    if not isinstance(e, jax.errors.JaxRuntimeError):
        return False
    msg = str(e)
    return any(m in msg for m in _XLA_RECOVERABLE_MARKERS)
