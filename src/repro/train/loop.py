"""The training loop: schedules, checkpoint/restart, failure recovery.

Fault-tolerance contract (DESIGN.md §19):
* auto-resume — on start, restore the newest checkpoint if one exists;
* typed fault injection — ``TrainLoopConfig.faults`` takes a deterministic
  ``comms.faults.FaultPlan``; host-side events (``step_crash``,
  ``slow_worker``) fire here, in-step events (``nan_grad``,
  ``payload_corrupt``) ride the reducer config into the jitted step;
* step-level recovery — a step failing with a host-side fault (any
  ``_RECOVERABLE`` error: an injected crash, a float trap) rolls back to the
  last checkpoint and retries; with no checkpoint yet it retries in place
  (nothing was committed), and the original error — not a
  ``FileNotFoundError`` from a hopeless restore — surfaces if recovery
  fails;
* no fallback for the device — an error from the JAX runtime (out of device
  memory, a kernel or program the compiler refuses) propagates at once: it
  is deterministic, so a retry cannot cure it, and walking the ladder would
  turn a broken run into one that looks healthy;
* degradation ladder — when retries are exhausted, or the non-finite guard
  keeps skipping steps, the loop walks ``reducers.degrade_config`` one
  rung at a time (pallas→reference, streamed→stacked, exotic transports→
  flat psum, compressed→dense) instead of raising; each transition lands
  in the run's ``ReducerHealth`` record.  Only a fully-degraded config
  that still fails propagates the error;
* theta/lr schedules — evaluated host-side per step; a *theta* change swaps
  the compiled step function (static kept-k), which is the recompile-bounded
  behaviour discussed in core/schedules.py.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Set

from jax.errors import JaxRuntimeError

from repro.comms import faults as faults_mod
from repro.comms import reducers
from repro.core.schedules import quantize_theta
from repro.train import checkpoint as ckpt
from repro.train.step import StepConfig, build_train_step

__all__ = ["TrainLoopConfig", "train_loop", "_RECOVERABLE"]


# Host-side faults the rollback/ladder path may absorb (injected crashes are
# RuntimeErrors).  JaxRuntimeError subclasses RuntimeError and is re-raised
# before this clause is reached: see the module docstring.
_RECOVERABLE = (RuntimeError, FloatingPointError)


@dataclasses.dataclass
class TrainLoopConfig:
    total_steps: int = 100
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    ckpt_keep: int = 3
    log_every: int = 10
    max_retries: int = 2
    theta_schedule: Optional[Callable[[int], float]] = None  # -> theta
    lr_schedule: Optional[Callable[[int], float]] = None  # -> multiplier
    # deterministic fault plan (comms/faults.py): step_crash / slow_worker
    # events fire host-side here; nan_grad / payload_corrupt events should
    # ALSO be set on the reducer config (ReducerConfig.faults) — they run
    # inside the jitted step
    faults: Optional[faults_mod.FaultPlan] = None
    # Called EVERY step (not just log_every) with (step, metrics, state) after
    # the step commits; metrics values are host floats.  The convergence lab
    # hangs its per-step recorder (loss / grad-energy / Assumption 3.1 probe)
    # here without changing the history contract below.
    metrics_hook: Optional[Callable[[int, Dict, Dict], None]] = None
    # Called EVERY committed step with (step, state) AFTER metrics_hook —
    # the serving publish path (serve/publish.py, DESIGN.md §20) hangs
    # WeightDeltaPublisher.hook() here; the publisher applies its own
    # publish_every cadence.  Kept separate from metrics_hook: it consumes
    # the state (not the metrics), and skipped steps still publish — the
    # replica fleet tracks committed weights, whatever the step did.
    publish_hook: Optional[Callable[[int, Dict], None]] = None
    # crash events that already fired, persisted ACROSS train_loop calls on
    # the same config: a restarted process does not re-hit a transient
    # crash, so fatal-crash + auto-resume runs complete (comms/faults.py)
    fired_faults: Set[int] = dataclasses.field(
        default_factory=set, repr=False, compare=False)


def train_loop(
    model,
    opt_cfg,
    step_cfg: StepConfig,
    mesh,
    state,
    stream,
    loop_cfg: TrainLoopConfig,
) -> Dict:
    """Runs the loop; returns {"state": ..., "history": [...], "health": {...}}."""
    manager = (
        ckpt.CheckpointManager(loop_cfg.ckpt_dir, loop_cfg.ckpt_every, loop_cfg.ckpt_keep)
        if loop_cfg.ckpt_dir
        else None
    )
    health = faults_mod.ReducerHealth()

    start_step = 0
    if manager is not None and ckpt.latest_step(loop_cfg.ckpt_dir) is not None:
        state, start_step = ckpt.restore(loop_cfg.ckpt_dir, state)
        print(f"[loop] resumed from step {start_step}")

    # the live step config: the degradation ladder replaces the reducer in
    # here and invalidates the compiled-step cache below
    live_cfg = step_cfg

    # compiled step cache keyed by (theta_bucket,) — schedule-driven rebuilds
    step_fns: Dict[float, Callable] = {}

    def get_step_fn(theta: Optional[float]):
        key = -1.0 if theta is None else theta
        if key not in step_fns:
            cfg = live_cfg
            if theta is not None and live_cfg.reducer is not None:
                cfg = dataclasses.replace(
                    live_cfg, reducer=dataclasses.replace(live_cfg.reducer, theta=theta)
                )
            example = stream.batch_at(0)
            step_fns[key] = build_train_step(model, opt_cfg, cfg, mesh, example)
        return step_fns[key]

    def degrade(at_step: int, reason: str) -> bool:
        """One rung down the ladder; False when there is nowhere to go."""
        nonlocal live_cfg, state
        if live_cfg.reducer is None:
            return False
        rung = reducers.degrade_config(live_cfg.reducer)
        if rung is None:
            return False
        new_reducer, label = rung
        if live_cfg.reducer.error_feedback and not new_reducer.error_feedback:
            # the dense rung has no compression loss to accumulate — drop
            # the residual from the state (and from future checkpoints)
            state = {k: v for k, v in state.items() if k != "residual"}
        live_cfg = dataclasses.replace(live_cfg, reducer=new_reducer)
        step_fns.clear()
        health.record_transition(at_step, label, reason)
        print(f"[loop] step {at_step}: degrading exchange — {label} ({reason})")
        return True

    history: List[Dict] = []
    step = start_step
    retries = 0
    consecutive_skips = 0
    while step < loop_cfg.total_steps:
        theta = None
        if loop_cfg.theta_schedule is not None:
            theta = quantize_theta(loop_cfg.theta_schedule(step))
        lr_scale = loop_cfg.lr_schedule(step) if loop_cfg.lr_schedule else 1.0
        try:
            if loop_cfg.faults is not None:
                for idx, ev in loop_cfg.faults.crashes_at(step):
                    if idx in loop_cfg.fired_faults:
                        continue
                    loop_cfg.fired_faults.add(idx)
                    if ev.fatal:
                        raise faults_mod.FatalInjectedCrash(
                            f"planned fatal crash at step {step}")
                    raise faults_mod.InjectedCrash(
                        f"planned crash at step {step}")
                delay = loop_cfg.faults.delay_at(step)
                if delay > 0:
                    health.record_delay(step)
                    time.sleep(delay)
            batch = stream.batch_at(step)
            step_fn = get_step_fn(theta)
            t0 = time.perf_counter()
            state, metrics = step_fn(state, batch)
            skipped = bool(float(metrics.get("skipped", 0.0)))
            if skipped:
                health.record_skip(step)
                consecutive_skips += 1
            else:
                consecutive_skips = 0
            if loop_cfg.metrics_hook is not None:
                hook_metrics = {k: float(v) for k, v in metrics.items()}
                hook_metrics.update(step=step, theta=theta,
                                    dt=time.perf_counter() - t0,
                                    degradations=len(health.transitions))
                loop_cfg.metrics_hook(step, hook_metrics, state)
            if loop_cfg.publish_hook is not None:
                loop_cfg.publish_hook(step, state)
            if step % loop_cfg.log_every == 0:
                metrics = {k: float(v) for k, v in metrics.items()}
                metrics.update(step=step, theta=theta, dt=time.perf_counter() - t0)
                history.append(metrics)
            step += 1
            retries = 0
            if manager is not None:
                manager.maybe_save(step, state)
            # the guard skipping step after step means the exchange itself is
            # producing garbage (poisoned payloads, broken kernels): walk the
            # ladder — skipped steps committed nothing, so no rollback needed
            if consecutive_skips > loop_cfg.max_retries:
                if degrade(step, f"{consecutive_skips} consecutive skipped steps"):
                    consecutive_skips = 0
        except JaxRuntimeError:
            raise  # out of memory, compile failure: no retry, no ladder
        except _RECOVERABLE as e:
            retries += 1
            if retries > loop_cfg.max_retries:
                if not degrade(step, f"step failure: {e}"):
                    raise
                retries = 0
            if (manager is not None
                    and ckpt.latest_step(loop_cfg.ckpt_dir) is not None):
                print(f"[loop] step {step} failed ({e}); "
                      f"rolling back to last checkpoint")
                state, step = ckpt.restore(loop_cfg.ckpt_dir, state)
            else:
                # nothing committed and nothing to restore: retry in place,
                # keeping the ORIGINAL error as what surfaces on exhaustion
                print(f"[loop] step {step} failed ({e}); "
                      f"no checkpoint yet — retrying in place")
    if manager is not None:
        ckpt.wait()
    return {"state": state, "history": history, "health": health.to_dict()}
