"""Generic fault-tolerant training loop.

Composes: a jit'd step function, a checkpointable data pipeline, the
CheckpointManager, and failure handling:

* periodic async checkpoints (params + optimizer state + pipeline step);
* automatic resume from the latest *intact* checkpoint (``run`` is
  re-entrant: a crashed/preempted process restarts and continues
  bit-exactly; a corrupted latest checkpoint falls back to the previous
  verified one);
* a fault-injection hook (see `health/inject.FaultInjector` for the
  schedule-driven implementation; any ``step -> None`` callable works,
  and callables with an ``attach`` method are handed the loop so they
  can tamper with live state / checkpoints);
* non-finite-loss / runtime-error circuit breaker (JAX's own runtime
  errors, from the compiler or the device, are re-raised): restore the latest
  checkpoint, or — when nothing has been checkpointed yet — the pristine
  *initial* state snapshotted at construction (the in-flight ``self.state``
  may hold a half-applied, corrupted update).  Loss scaling is the
  optimizer's concern, not the loop's.  The practical straggler/failure
  posture for SPMD jobs is checkpoint-restart, since a lock-step
  collective cannot outrun its slowest participant (see DESIGN.md §5);
* an optional `health/watchdog.Watchdog`: fed each completed step's
  metrics; its ``Escalate`` actions swap ``step_fn`` in place (graceful
  precision degradation) and its ``Rollback`` actions reuse the circuit
  breaker's restore path.

The restart budget is *windowed*: ``config.restart_window`` bounds how
many failures may land within any sliding span of that many steps, so a
transient fault at step 10 doesn't consume the budget of a million-step
run while ``max_restarts`` back-to-back failures still abort
(``restart_window=None`` keeps the legacy run-lifetime budget).
"""
from __future__ import annotations

import copy
import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.checkpoint import CheckpointManager


@dataclasses.dataclass
class TrainLoopConfig:
    total_steps: int
    checkpoint_every: int = 50
    checkpoint_dir: str = "/tmp/repro_ckpt"
    keep_checkpoints: int = 3
    log_every: int = 10
    max_restarts: int = 3
    # sliding step window the restart budget is counted over; None = the
    # legacy behaviour (max_restarts over the whole run's lifetime)
    restart_window: Optional[int] = None
    # grid name / canonical spec name for packed low-precision checkpoint
    # leaves (checkpoint/manager.py pack_np); None = raw float32
    checkpoint_fmt: Optional[str] = None
    # number of leaves.npz shard files per checkpoint
    checkpoint_shards: int = 4


class TrainLoop:
    def __init__(self, step_fn: Callable, pipeline, init_state,
                 config: TrainLoopConfig,
                 fault_hook: Optional[Callable[[int], None]] = None,
                 metrics_hook: Optional[Callable[[int, Dict], None]] = None,
                 state_sharding=None, watchdog=None):
        """step_fn(state, batch) -> (state, metrics dict of scalars).

        ``state_sharding``: optional pytree of shardings matching
        ``init_state`` — checkpoint restores then re-place the host
        arrays directly onto the mesh layout (sharded resume), instead
        of bouncing them through the default device.

        ``watchdog``: optional `health/watchdog.Watchdog` — observes each
        completed step's metrics and may escalate precision (swapping
        ``step_fn`` via its rebuild hook) or demand a rollback.
        """
        self.step_fn = step_fn
        self.pipeline = pipeline
        self.state = init_state
        self.state_sharding = state_sharding
        self.watchdog = watchdog
        # pristine snapshot for checkpoint-less restarts: jax arrays are
        # immutable, so holding the initial tree is enough; the pipeline
        # state dict is copied because pipelines mutate in place
        self._init_state = init_state
        self._init_pipeline = copy.deepcopy(pipeline.state_dict())
        self.config = config
        self.fault_hook = fault_hook
        if fault_hook is not None and hasattr(fault_hook, "attach"):
            fault_hook.attach(self)
        self.metrics_hook = metrics_hook
        self.ckpt = CheckpointManager(config.checkpoint_dir,
                                      keep=config.keep_checkpoints,
                                      fmt=config.checkpoint_fmt,
                                      shards=config.checkpoint_shards)
        self.history: list = []

    # ------------------------------------------------------------------ io
    def _save(self, step: int, blocking=False):
        payload = {"state": self.state,
                   "pipeline": self.pipeline.state_dict()}
        self.ckpt.save(step, payload, blocking=blocking)

    def _try_resume(self) -> int:
        try:
            # newest *intact* checkpoint: restore() checksum-verifies and
            # falls back past corrupted steps on its own
            latest, payload, _ = self.ckpt.restore()
        except FileNotFoundError:
            # nothing restorable: fall back to the pristine initial state —
            # the in-flight self.state may be a corrupted half-step
            if self._init_state is None:
                raise
            self.state = self._init_state
            self.pipeline.load_state_dict(
                copy.deepcopy(self._init_pipeline))
            resumed = 0
        else:
            if self.state_sharding is not None:
                self.state = jax.device_put(payload["state"],
                                            self.state_sharding)
            else:
                self.state = jax.tree.map(jax.numpy.asarray,
                                          payload["state"])
            self.pipeline.load_state_dict(payload["pipeline"])
            resumed = latest
        # drop history from the discarded run segment: the replayed steps
        # append fresh entries (otherwise the BENCH trajectory would carry
        # duplicate step numbers with stale losses)
        self.history = [h for h in self.history if h["step"] <= resumed]
        return resumed

    # ----------------------------------------------------------------- run
    def _charge_restart(self, restart_log: List[int], step: int) -> None:
        """Windowed restart budget; raises via the caller when exceeded."""
        window = self.config.restart_window
        if window:
            # keep only failures within the trailing window of *steps*
            restart_log[:] = [s for s in restart_log if s > step - window]
        restart_log.append(step)
        if len(restart_log) > self.config.max_restarts:
            raise RuntimeError(
                f"restart budget exhausted: {len(restart_log)} failures "
                + (f"within {window} steps" if window else "this run")
                + f" (max_restarts={self.config.max_restarts})")

    def run(self) -> Dict[str, Any]:
        cfg = self.config
        start = self._try_resume()
        step = start
        restart_log: List[int] = []   # in-window step numbers of failures
        restarts_total = 0
        # wall-time accounting: feeds the step_ms column in the history and
        # the perf trajectory in BENCH_kernels.json (benchmarks/run.py)
        window_t, window_n = 0.0, 0
        total_t, total_n = 0.0, 0
        while step < cfg.total_steps:
            try:
                if self.fault_hook is not None:
                    self.fault_hook(step)
                batch = self.pipeline.next()
                t0 = time.perf_counter()
                self.state, metrics = self.step_fn(self.state, batch)
                jax.block_until_ready(self.state)
                dt = time.perf_counter() - t0
                window_t += dt
                window_n += 1
                total_t += dt
                total_n += 1
                loss = float(metrics.get("loss", np.nan))
                if not np.isfinite(loss):
                    raise FloatingPointError(f"non-finite loss at {step}")
                step += 1
                self._observe_watchdog(step, metrics)
                if step % cfg.log_every == 0 or step == cfg.total_steps:
                    self.history.append({
                        "step": step,
                        "step_ms": 1e3 * window_t / max(window_n, 1),
                        **{k: float(v) for k, v in metrics.items()}})
                    window_t, window_n = 0.0, 0
                    if self.metrics_hook:
                        self.metrics_hook(step, metrics)
                if step % cfg.checkpoint_every == 0:
                    self._save(step)
                    if (self._init_state is not None
                            and self.ckpt.latest_step() is not None):
                        # a durable checkpoint now covers restart: release
                        # the pristine snapshot (it pins params + optimizer
                        # state on device); async saves may defer this to
                        # the next checkpoint boundary
                        self._init_state = None
                        self._init_pipeline = None
            except (FloatingPointError, RuntimeError) as e:
                if isinstance(e, jax.errors.JaxRuntimeError):
                    # a compiler or device error: replaying the step from
                    # a checkpoint would only repeat it
                    raise
                restarts_total += 1
                self._charge_restart(restart_log, step)
                resumed = self._try_resume()
                step = resumed
                # the interrupted window's timings belong to discarded steps
                window_t, window_n = 0.0, 0
                continue
        self._save(step, blocking=True)
        self.ckpt.wait()
        out = {"final_step": step, "restarts": restarts_total,
               "history": self.history, "state": self.state,
               "mean_step_ms": 1e3 * total_t / max(total_n, 1)}
        if self.watchdog is not None:
            out["watchdog_events"] = list(self.watchdog.events)
        return out

    def _observe_watchdog(self, step: int, metrics: Dict[str, Any]) -> None:
        if self.watchdog is None:
            return
        from repro.health.watchdog import Escalate, Rollback
        action = self.watchdog.observe(step, metrics)
        if isinstance(action, Escalate):
            if action.step_fn is not None:
                self.step_fn = action.step_fn
        elif isinstance(action, Rollback):
            # reuse the circuit breaker: the raise lands in run()'s except
            # handler, which charges the restart budget and restores the
            # newest intact checkpoint (or the pristine init state)
            raise FloatingPointError(
                f"watchdog rollback at step {step}: {action.trigger}")
