"""Fault-tolerant runtime: the supervised ResilientLoop + straggler policy.

On-device (and at 1000+ node scale) the failure model is: (a) whole-job
preemption/crash — handled by atomic checkpoints + auto-resume; (b) memory
pressure / ``RESOURCE_EXHAUSTED`` — handled by the degradation ladder
(``runtime/degrade.py``) before falling back to retry; (c) numerical
anomalies (NaN loss, gradient spikes) — handled by the step guard
(``runtime/guard.py``) with a bounded skip-and-rewind budget; (d) hangs /
stragglers — a per-step watchdog whose ``restart`` verdict triggers a
supervised restore-from-checkpoint (bounded by ``restart_budget``);
(e) data-loss on restart — prevented by checkpointing the data-iterator
state.

:class:`ResilientLoop` is the supervisor: it owns the step/retry state
machine, classifies failures (OOM vs transient), applies exponential
backoff, **resets the retry budget once a step past the failed one
succeeds** (one transient early plus another much later must not kill a
long run, while a failure that recurs every time its step is replayed from
an earlier checkpoint still exhausts the budget), counts
every fault into :class:`FaultCounters`, and always force-saves a final
checkpoint on exit so a completed run is resumable/servable even when
``total_steps % interval != 0``.

``run_resilient`` remains as the thin functional wrapper used by older
call sites and tests; it runs the same loop with ``restart_budget=0``
(straggler restarts raise, the historical contract).
"""
from __future__ import annotations

import dataclasses
import logging
import math
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
from jax.profiler import StepTraceAnnotation

from repro.checkpoint import Checkpointer
from repro.runtime.faults import is_oom_error
from repro.runtime.guard import update_sq_norm
from repro.telemetry.metrics import CounterGroup

log = logging.getLogger("repro.ft")


@dataclass
class StepResult:
    step: int
    loss: float
    seconds: float
    retried: bool = False


@dataclass
class FaultCounters:
    """Per-fault accounting surfaced in ``TrainResult`` and the chaos
    benchmark's ``BENCH_resilience.json``."""
    step_failures: int = 0        # generic exceptions (incl. crashes)
    oom_events: int = 0           # RESOURCE_EXHAUSTED-class failures
    degradations: int = 0         # ladder rungs applied
    watermark_triggers: int = 0   # proactive degrades from measured pressure
    guard_skips: int = 0          # anomalous steps rejected + rewound
    straggler_restarts: int = 0   # watchdog-triggered supervised restarts
    ckpt_quarantines: int = 0     # corrupt checkpoints quarantined
    steps_replayed: int = 0       # steps re-run after restore rewinds
    backoff_seconds: float = 0.0  # total time spent backing off
    injected: dict = field(default_factory=dict)  # {kind: fired} from plan

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @property
    def total_faults(self) -> int:
        return (self.step_failures + self.oom_events + self.guard_skips
                + self.straggler_restarts)


class StragglerPolicy:
    """EWMA step-time tracker; flags steps slower than ``factor``× the mean.

    The first ``warmup`` observations are discarded from the baseline (the
    jit-compile step would otherwise seed the EWMA with a wildly unhistoric
    mean). On real hardware a flagged step triggers (1) collective-timeout
    logging, (2) optional step skip for async-capable optimizers, (3) a
    restart signal if ``consecutive_limit`` is exceeded (the node is
    presumed sick).
    """

    def __init__(self, factor: float = 3.0, consecutive_limit: int = 3,
                 alpha: float = 0.1, warmup: int = 1):
        self.factor = factor
        self.limit = consecutive_limit
        self.alpha = alpha
        self.warmup = warmup
        self._seen = 0
        self.mean: Optional[float] = None
        self.slow_streak = 0

    def reset(self) -> None:
        """Re-seed the baseline (after a restart or a re-jitted step)."""
        self._seen = 0
        self.mean = None
        self.slow_streak = 0

    def observe(self, seconds: float) -> str:
        """Returns 'ok' | 'slow' | 'restart'."""
        self._seen += 1
        if self._seen <= self.warmup:
            return "ok"                      # compile step: not a baseline
        if self.mean is None:
            self.mean = seconds
            return "ok"
        verdict = "ok"
        if seconds > self.factor * self.mean:
            self.slow_streak += 1
            verdict = "restart" if self.slow_streak >= self.limit else "slow"
        else:
            self.slow_streak = 0
        # slow steps don't poison the EWMA baseline
        if verdict == "ok":
            self.mean = (1 - self.alpha) * self.mean + self.alpha * seconds
        return verdict


class RestartRequired(RuntimeError):
    pass


class ResilientLoop:
    """Supervised training-step driver.

    step_fn(params, opt_state, batch) -> (params, opt_state, loss[, aux])
    init_state() -> (params, opt_state)

    Pluggable hooks (all optional) let the Trainer facade wire in the full
    resilience stack without this module importing any of it eagerly:

    * ``injector``   — :class:`~repro.runtime.faults.FaultInjector`; its
      ``before_step`` runs inside the try block (raising kinds land in the
      failure handler) and ``after_step`` may replace the loss.
    * ``guard``      — :class:`~repro.runtime.guard.StepGuard`; a ``reject``
      verdict rewinds the step (new params/opt-state discarded, batch
      skipped).
    * ``on_oom(loop)`` — degradation hook. May swap ``loop.step_fn`` /
      ``loop.batch_iter`` and return transformed ``(params, opt_state)`` to
      retry the same step under a cheaper spec; ``None`` falls through to
      the ordinary retry path.
    * ``restore_fn(loop)`` — replaces the default restore (the Trainer uses
      this to rebuild engine/iterator from the spec recorded in the
      checkpoint manifest). Must return ``(step, params, opt_state)`` and
      update ``loop.batch_iter``/``loop.step_fn`` as needed.
    * ``extra_fn()`` — dict merged into every checkpoint manifest (the
      Trainer records the live spec so restores are self-describing).
    * ``telemetry`` — :class:`repro.telemetry.Telemetry`; when enabled the
      loop emits typed step/fault/checkpoint/watermark events, records its
      spans into ``trace.json`` and keeps ``train.*`` metrics. Enabled or
      not, each step attempt is a ``StepTraceAnnotation("train")`` and its
      phases are profiler annotations (``telemetry.spans.LOOP_SPANS``);
      disabled, that is all the hot path pays — same jitted step object,
      no record built (asserted by tests/test_telemetry.py).
    * ``train_counters`` — :class:`~repro.telemetry.metrics.CounterGroup`
      ``train`` (``steps`` completed, ``host_syncs``: device→host reads, one
      a step — the loss, with the guard's squared update norm when it
      tracks one); registered with an enabled telemetry.
    * ``aux_counters`` — :class:`~repro.telemetry.metrics.CounterGroup`
      that a step's fourth output (a dict of integer counters, such as the
      expert layers' ``moe.*``) is added to when the step commits; it is
      read in the loss's sync, never in one of its own.
    * ``memwatch`` — :class:`repro.telemetry.MemoryWatermark`; sampled after
      every successful step.
    * ``pressure`` — :class:`repro.runtime.degrade.WatermarkTrigger`; fed
      the watermark samples, and when it trips the loop walks the same
      ``on_oom`` ladder *before* the allocator actually fails.
    """

    def __init__(self, step_fn: Callable[[Any, Any, dict], tuple],
                 init_state: Callable[[], tuple],
                 batch_iter,
                 ckpt: Checkpointer,
                 total_steps: int,
                 *,
                 max_retries: int = 3,
                 restart_budget: int = 0,
                 backoff_base: float = 0.05,
                 backoff_max: float = 30.0,
                 straggler: Optional[StragglerPolicy] = None,
                 guard=None,
                 injector=None,
                 on_step: Optional[Callable[[StepResult], None]] = None,
                 on_oom: Optional[Callable] = None,
                 restore_fn: Optional[Callable] = None,
                 extra_fn: Optional[Callable[[], dict]] = None,
                 telemetry=None,
                 memwatch=None,
                 pressure=None,
                 train_counters: Optional[CounterGroup] = None,
                 aux_counters: Optional[CounterGroup] = None):
        self.step_fn = step_fn
        self.init_state = init_state
        self.batch_iter = batch_iter
        self.ckpt = ckpt
        self.total_steps = total_steps
        self.max_retries = max_retries
        self.restart_budget = restart_budget
        self.backoff_base = backoff_base
        self.backoff_max = backoff_max
        self.straggler = straggler or StragglerPolicy()
        self.guard = guard
        self.injector = injector
        self.on_step = on_step
        self.on_oom = on_oom
        self.restore_fn = restore_fn
        self.extra_fn = extra_fn
        if telemetry is None:
            from repro.telemetry import DISABLED
            telemetry = DISABLED
        self.telemetry = telemetry
        self.train_counters = (train_counters if train_counters is not None
                               else CounterGroup("train",
                                                 ("steps", "host_syncs")))
        self.aux_counters = aux_counters
        if telemetry.enabled:
            telemetry.registry.register_group(self.train_counters)
            if aux_counters is not None:
                telemetry.registry.register_group(aux_counters)
        self._steps = self.train_counters.counter("steps")
        self._syncs = self.train_counters.counter("host_syncs")
        self.memwatch = memwatch
        self.pressure = pressure
        #: why the current on_oom invocation happened ("oom" | "watermark");
        #: read by the Trainer's degrade hook to tag its DegradeEvent
        self.degrade_trigger = "oom"

        self.counters = FaultCounters()
        self.step = 0
        self.params = None
        self.opt_state = None
        self._consecutive_failures = 0
        self._failed_step = -1
        self._last_saved: Optional[int] = None
        # snapshot of the iterator's initial position so a restore with no
        # checkpoint replays the exact token stream from the start
        state = getattr(batch_iter, "state", None)
        self._initial_data_state = (dataclasses.replace(state)
                                    if dataclasses.is_dataclass(state)
                                    else None)

    # -------------------------------------------------------------- restore
    def _data_state_dict(self) -> Optional[dict]:
        state = getattr(self.batch_iter, "state", None)
        return state.to_dict() if state is not None else None

    def _restore(self):
        with self.telemetry.span("train/restore"):
            return self._restore_inner()

    def _restore_inner(self):
        self.straggler.reset()
        t0 = time.monotonic()
        if self.restore_fn is not None:
            step, params, opt_state = self.restore_fn(self)
        else:
            params, opt_state = self.init_state()
            restored = self.ckpt.restore_latest(params, opt_state)
            if restored is not None:
                log.info("resuming from step %d", restored["step"])
                if restored["data_state"]:
                    self.batch_iter.state = type(
                        self.batch_iter.state).from_dict(
                        restored["data_state"])
                step, params, opt_state = (restored["step"],
                                           restored["params"],
                                           restored["opt_state"])
            else:
                step = 0
                if self._initial_data_state is not None:
                    self.batch_iter.state = dataclasses.replace(
                        self._initial_data_state)
        if step < self.step:
            self.counters.steps_replayed += self.step - step
        prev_quar = self.counters.ckpt_quarantines
        self.counters.ckpt_quarantines = len(
            getattr(self.ckpt, "quarantined", ()))
        tel = self.telemetry
        if tel.enabled:
            from repro.telemetry import CheckpointEvent
            tel.emit(CheckpointEvent(action="restore", step=step,
                                     seconds=time.monotonic() - t0,
                                     path=self.ckpt.directory))
            for _ in range(self.counters.ckpt_quarantines - prev_quar):
                tel.emit(CheckpointEvent(action="quarantine", step=step,
                                         path=self.ckpt.directory))
            tel.registry.counter("ckpt.restores").inc()
        return step, params, opt_state

    # ----------------------------------------------------------------- save
    def _save_now(self) -> None:
        t0 = time.monotonic()
        with self.telemetry.span("train/checkpoint"):
            self.ckpt.save(self.step, self.params, self.opt_state,
                           data_state=self._data_state_dict(),
                           extra=self.extra_fn() if self.extra_fn else None)
        self._last_saved = self.step
        tel = self.telemetry
        if tel.enabled:
            from repro.telemetry import CheckpointEvent
            tel.emit(CheckpointEvent(action="save", step=self.step,
                                     seconds=time.monotonic() - t0,
                                     path=self.ckpt.directory))
            tel.registry.counter("ckpt.saves").inc()

    # -------------------------------------------------------------- failure
    def _handle_failure(self, e: BaseException) -> None:
        oom = is_oom_error(e)
        tel = self.telemetry
        if tel.enabled:
            from repro.telemetry import FaultEvent as TelFault
            tel.emit(TelFault(step=self.step,
                              fault="oom" if oom else "exception",
                              injected=type(e).__name__.startswith("Injected"),
                              source="loop", error=str(e)))
            tel.registry.counter(
                "faults.oom" if oom else "faults.exception").inc()
        if oom:
            self.counters.oom_events += 1
            log.warning("step %d hit memory pressure: %s", self.step, e)
            if self.on_oom is not None:
                swapped = self.on_oom(self)
                if swapped is not None:
                    self.params, self.opt_state = swapped
                    self.counters.degradations += 1
                    self.straggler.reset()   # next step re-jits: not slow
                    # checkpoint the degraded state immediately so any later
                    # restore reconstitutes the post-degradation program
                    self._save_now()
                    return
        else:
            self.counters.step_failures += 1
        self._consecutive_failures += 1
        self._failed_step = max(self._failed_step, self.step)
        log.warning("step %d failed (%s); retry %d/%d from checkpoint",
                    self.step, e, self._consecutive_failures,
                    self.max_retries)
        if self._consecutive_failures > self.max_retries:
            raise
        delay = min(self.backoff_max,
                    self.backoff_base * (2 ** (self._consecutive_failures
                                               - 1)))
        if delay > 0:
            self.counters.backoff_seconds += delay
            time.sleep(delay)
        self.step, self.params, self.opt_state = self._restore()

    # ---------------------------------------------------------- memwatch
    def _sample_watermark(self) -> None:
        """Post-step watermark sample: metrics/event, then pressure check."""
        m = self.memwatch.sample()
        pred = self.memwatch.predicted_mb
        tel = self.telemetry
        if tel.enabled:
            from repro.telemetry import WatermarkEvent
            tel.registry.gauge("mem.measured_mb").set(m["measured_mb"])
            tel.registry.gauge("mem.peak_mb").set(m["peak_mb"])
            tel.emit(WatermarkEvent(
                step=self.step, measured_mb=round(m["measured_mb"], 3),
                peak_mb=round(m["peak_mb"], 3),
                predicted_mb=round(pred or 0.0, 3),
                ratio=round(m["peak_mb"] / pred, 4) if pred else 0.0,
                source=m["source"]))
        if self.pressure is not None \
                and self.pressure.observe(m["measured_mb"]):
            self._degrade_for_pressure(m["measured_mb"])

    def _degrade_for_pressure(self, measured_mb: float) -> None:
        """Walk the on_oom ladder proactively, before the allocator fails."""
        if self.on_oom is None:
            self.pressure = None
            return
        self.counters.watermark_triggers += 1
        log.warning("watermark pressure: %.1f MB >= %.1f MB limit at step "
                    "%d; degrading proactively", measured_mb,
                    self.pressure.limit_mb, self.step)
        self.degrade_trigger = "watermark"
        try:
            swapped = self.on_oom(self)
        finally:
            self.degrade_trigger = "oom"
        if swapped is not None:
            self.params, self.opt_state = swapped
            self.counters.degradations += 1
            self.straggler.reset()
            self._save_now()
        else:
            # ladder exhausted: nothing cheaper exists, stop re-checking
            log.warning("watermark pressure with no rung left; trigger "
                        "disabled for the rest of the run")
            self.pressure = None

    # ------------------------------------------------------------------ run
    def run(self):
        self.step, self.params, self.opt_state = self._restore()
        results = []
        while self.step < self.total_steps:
            with StepTraceAnnotation("train", step_num=self.step):
                self._attempt(results)
        # forced final save: a completed run is always resumable/servable
        # from its last step, even when total_steps % interval != 0
        if self.step > 0 and self._last_saved != self.step:
            self._save_now()
        if self.injector is not None:
            self.counters.injected = self.injector.summary()
        self.counters.ckpt_quarantines = len(
            getattr(self.ckpt, "quarantined", ()))
        return self.params, self.opt_state, results, self.counters

    def _attempt(self, results: list) -> None:
        """One attempt at step ``self.step``: commits it (appending its
        :class:`StepResult`), or rewinds, retries or restores."""
        tel = self.telemetry
        t0 = time.monotonic()
        track_norm = (self.guard is not None
                      and self.guard.track_update_norm)
        try:
            if self.injector is not None:
                self.injector.before_step(self.step)
            with tel.span("train/data"):
                batch = next(self.batch_iter)
            with tel.span("train/dispatch"):
                new_params, new_opt, loss, *aux = self.step_fn(
                    self.params, self.opt_state, batch)
                # queued behind the step, read with the loss below
                sq_norm = (update_sq_norm(self.params, new_params)
                           if track_norm else None)
            if self.injector is not None:
                loss = self.injector.after_step(self.step, loss)
            with tel.span("train/loss_sync"):
                if track_norm or aux:
                    loss, sq_norm, aux = jax.device_get((loss, sq_norm, aux))
                lossf = float(loss)
            self._syncs.inc()
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:
            self._handle_failure(e)
            return
        if self.guard is not None:
            with tel.span("train/guard"):
                unorm = math.sqrt(sq_norm) if track_norm else None
                verdict = self.guard.observe(lossf, update_norm=unorm,
                                             step=self.step)
            if verdict == "reject":
                self.counters.guard_skips += 1
                return        # rewind: update discarded, batch skipped
        dt = time.monotonic() - t0
        verdict = self.straggler.observe(dt)
        if verdict == "restart":
            self.counters.straggler_restarts += 1
            if self.counters.straggler_restarts > self.restart_budget:
                raise RestartRequired(
                    f"step {self.step}: {dt:.1f}s >= "
                    f"{self.straggler.factor}x EWMA for "
                    f"{self.straggler.limit} consecutive steps")
            log.warning("straggler watchdog: supervised restart %d/%d "
                        "at step %d (%.1fs step)",
                        self.counters.straggler_restarts,
                        self.restart_budget, self.step, dt)
            self.step, self.params, self.opt_state = self._restore()
            return
        elif verdict == "slow":
            log.warning("step %d slow: %.2fs vs EWMA %.2fs",
                        self.step, dt, self.straggler.mean or 0.0)
        self.params, self.opt_state = new_params, new_opt
        self.step += 1
        self._steps.inc()
        if aux and self.aux_counters is not None:
            for k, v in aux[0].items():
                self.aux_counters[k] += int(v)
        if self.step > self._failed_step:
            self._consecutive_failures = 0   # past the failure: reset
        res = StepResult(self.step, lossf, dt,
                         retried=self.counters.total_faults > 0)
        results.append(res)
        if tel.enabled:
            from repro.telemetry import StepEvent
            tel.emit(StepEvent(step=self.step, loss=lossf, seconds=dt))
            tel.registry.gauge("train.loss").set(lossf)
            tel.registry.histogram("train.step_seconds").record(dt)
        if self.memwatch is not None:
            with tel.span("train/watermark"):
                self._sample_watermark()
        if self.on_step:
            with tel.span("train/on_step"):
                self.on_step(res)
        with tel.span("train/checkpoint"):
            saved = self.ckpt.maybe_save(
                self.step, self.params, self.opt_state,
                data_state=self._data_state_dict(),
                extra=self.extra_fn() if self.extra_fn else None)
        if saved:
            self._last_saved = self.step
            if tel.enabled:
                from repro.telemetry import CheckpointEvent
                tel.emit(CheckpointEvent(action="save", step=self.step,
                                         path=self.ckpt.directory))
                tel.registry.counter("ckpt.saves").inc()


def run_resilient(step_fn: Callable[[Any, Any, dict], tuple],
                  init_state: Callable[[], tuple],
                  batch_iter,
                  ckpt: Checkpointer,
                  total_steps: int,
                  *,
                  max_retries: int = 3,
                  straggler: Optional[StragglerPolicy] = None,
                  on_step: Optional[Callable[[StepResult], None]] = None):
    """Functional wrapper over :class:`ResilientLoop` (historical API).

    Keeps the original contract: straggler ``restart`` verdicts raise
    :class:`RestartRequired` (``restart_budget=0``) and the return value is
    ``(params, opt_state, results)`` without counters.
    """
    loop = ResilientLoop(step_fn, init_state, batch_iter, ckpt, total_steps,
                         max_retries=max_retries, restart_budget=0,
                         straggler=straggler, on_step=on_step)
    params, opt_state, results, _ = loop.run()
    return params, opt_state, results
