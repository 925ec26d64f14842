"""Trainer facade: ``Trainer.from_spec(spec).fit(steps)``.

Wraps everything a training run needs around a TrainSpec: config resolution,
engine lookup + validation, optimizer, restartable data pipeline, atomic
checkpointing and the supervised resilient step driver
(``runtime.fault_tolerance.ResilientLoop``) with the full chaos stack —
deterministic fault injection (``--inject-faults``), the memory-pressure
degradation ladder (``runtime/degrade.py``) and the anomaly step guard
(``runtime/guard.py``). ``launch/train.py``, ``examples/finetune_e2e.py``
and the smoke CI all run through this facade.

The trainer is *re-specable* mid-run: every checkpoint manifest records the
spec that produced it, so a restore after a crash reconstitutes the exact
(possibly degraded) program, and an OOM walks the ladder to a cheaper spec
while carrying the optimizer state across compatible transitions.
"""
from __future__ import annotations

import contextlib
import dataclasses
import logging
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp

from repro.api.registry import Engine, get_engine
from repro.api.spec import TrainSpec

log = logging.getLogger("repro.trainer")


@dataclasses.dataclass
class TrainResult:
    params: Any
    opt_state: Any
    history: List  # of runtime.fault_tolerance.StepResult
    #: runtime.fault_tolerance.FaultCounters — per-fault accounting for the
    #: run (retries, OOMs, degradations, guard skips, restarts, quarantines)
    counters: Any = None
    #: the TrainSpec the run *ended* on (differs from the requested spec
    #: when the degradation ladder stepped down under memory pressure)
    final_spec: Optional[TrainSpec] = None
    #: ladder rungs applied, in order (e.g. ["halve_batch", "quantize_int8"])
    degradations: List[str] = dataclasses.field(default_factory=list)
    #: telemetry snapshot: guard state always (when guarded); with
    #: ``--telemetry on`` also the metric registry, events-by-kind, span
    #: totals and the measured-vs-memsim watermark comparison
    metrics: dict = dataclasses.field(default_factory=dict)

    @property
    def final_loss(self) -> float:
        return self.history[-1].loss if self.history else float("nan")

    @property
    def fault_counts(self) -> dict:
        return self.counters.to_dict() if self.counters is not None else {}


#: TrainSpec fields recorded into checkpoint manifests (JSON-safe subset —
#: everything that round-trips through the CLI)
_SPEC_FIELDS = tuple(f.name for f in dataclasses.fields(TrainSpec)
                     if f.metadata.get("cli", True))


def _spec_manifest(spec: TrainSpec) -> dict:
    return {name: getattr(spec, name) for name in _SPEC_FIELDS}


class Trainer:
    """One training run, fully described by a TrainSpec.

    ``cfg`` overrides the spec's ``arch``/``reduced`` resolution with an
    explicit ArchConfig (used by examples that build custom configs).
    ``counters`` (``train.steps``, ``train.host_syncs``) count over every
    ``fit`` of this trainer: steps completed and the loop's device→host
    reads (one a step: the loss, with the guard's update norm while the
    guard runs). ``moe_counters`` (``moe.routed_rows``,
    ``moe.computed_rows``, ``moe.dropped``; ``None`` for a model without
    counters, ``model.aux_names``) sum the expert layers' counters over
    the committed steps: a gradient engine's step returns them beside the
    loss and the loop reads them in the same sync. The zeroth-order
    engines' steps return none, and leave them at zero.
    """

    def __init__(self, spec: TrainSpec, *, cfg=None, mesh=None):
        from repro.configs import get_config
        from repro.optim import make_optimizer
        from repro.optim.schedules import constant
        from repro.telemetry.metrics import CounterGroup

        self.spec = spec.validate()
        if cfg is None:
            cfg = get_config(spec.arch)
            if spec.reduced:
                cfg = cfg.reduced()
        self.cfg = cfg
        self.opt = make_optimizer(spec.optimizer, constant(spec.lr))
        self.mesh = mesh if mesh is not None else self._auto_mesh(self.spec)
        self.counters = CounterGroup("train", ("steps", "host_syncs"))
        from repro.models.model import aux_names
        names = aux_names(cfg)
        self.moe_counters = CounterGroup("moe", names) if names else None
        self._live_spec: Optional[TrainSpec] = None
        self._switch_to(self.spec)

    @classmethod
    def from_spec(cls, spec: TrainSpec, *, cfg=None, mesh=None) -> "Trainer":
        return cls(spec, cfg=cfg, mesh=mesh)

    # -------------------------------------------------------------- sharding
    @staticmethod
    def _auto_mesh(spec: TrainSpec):
        """(data, model) mesh over the visible devices; ``None`` (unsharded,
        the historical single-device behaviour) with one device and
        ``model_parallel == 1``."""
        n = len(jax.devices())
        if n == 1 and spec.model_parallel == 1:
            return None
        from repro.runtime.elastic import make_mesh_from_devices
        return make_mesh_from_devices(jax.devices(), spec.model_parallel)

    def _with_mesh_act_spec(self, spec: TrainSpec) -> TrainSpec:
        """Fold the mesh's activation sharding into the spec (Megatron SP on
        the seq dim only when it divides). Under a Trainer-managed mesh
        act_spec is *derived* state, recomputed on every switch — a
        degradation rung that halves the batch or truncates the seq must not
        carry the old mesh geometry. Engines with a custom regime
        (``backend is None``, e.g. the ZO family) keep act_spec unset."""
        if self.mesh is None:
            return spec
        if get_engine(spec.engine).backend is None:
            return spec
        from repro.launch import sharding as sh
        msize = self.mesh.shape.get("model", 1)
        act = sh.activation_spec(
            self.mesh, spec.batch,
            seq_on_model=(msize > 1 and spec.seq % msize == 0))
        return dataclasses.replace(spec, act_spec=act)

    def _state_struct(self, spec: TrainSpec):
        """(params, opt_state) ShapeDtypeStructs for ``spec`` — no arrays."""
        from repro.models import model as model_lib

        def init():
            params = model_lib.init_params(
                jax.random.PRNGKey(self.spec.seed), self.cfg,
                quantize=spec.quantize)
            return params, self.opt.init(params)

        return jax.eval_shape(init)

    def shard_state(self, params, opt_state=None, *, mesh=None):
        """``device_put`` state onto the mesh's logical PartitionSpecs
        (placement-only — values are untouched, tested bit-exact). Returns
        ``params`` or ``(params, opt_state)`` mirroring the arguments."""
        from repro.launch import sharding as sh
        from repro.runtime.elastic import reshard_tree

        mesh = mesh if mesh is not None else self.mesh
        if mesh is None:
            return params if opt_state is None else (params, opt_state)
        params = reshard_tree(params, mesh,
                              sh.param_specs(self.cfg, params, mesh))
        if opt_state is None:
            return params
        opt_state = reshard_tree(opt_state, mesh,
                                 sh.opt_specs(self.cfg, opt_state, mesh))
        return params, opt_state

    def resize(self, devices=None, *, model_parallel=None, params=None,
               opt_state=None):
        """Elastic resize: rebuild the mesh from the surviving ``devices``
        (default: all visible), re-jit the live spec's step for it, and —
        when ``params``/``opt_state`` are passed — reshard them onto the new
        topology (``runtime.elastic.reshard_tree``; placement-only).

        Returns ``None``, ``params`` or ``(params, opt_state)`` mirroring
        the state arguments. The optimizer trajectory across a resize is
        covered by the emulated-fleet suite (tests/multihost/)."""
        from repro.runtime.elastic import make_mesh_from_devices

        devices = list(devices) if devices is not None else jax.devices()
        if model_parallel is None:
            model_parallel = (self.mesh.shape.get("model", 1)
                              if self.mesh is not None
                              else self.live_spec.model_parallel)
        self.mesh = make_mesh_from_devices(devices, model_parallel)
        live = dataclasses.replace(self.live_spec, act_spec=None)
        self._live_spec = None    # force a re-jit onto the new mesh
        self._switch_to(live)
        if params is None:
            return None
        return self.shard_state(params, opt_state)

    # ------------------------------------------------------------ live spec
    def _switch_to(self, spec: TrainSpec) -> None:
        """(Re)build engine + jitted step for ``spec``; no-op if unchanged.
        Raises (without changing live state) when the engine refuses the
        spec — the degradation path uses that to skip unbuildable rungs.

        With a mesh, the step is jitted with explicit in/out shardings
        (params/opt state on ``launch/sharding.py``'s logical specs, batch
        on the DP axes; the loss, and the counters a step may return after
        it, replicated) and wrapped to run inside the mesh context so
        ``with_sharding_constraint``/``mesh_axis_size`` see it."""
        spec = self._with_mesh_act_spec(spec)
        if spec == self._live_spec:
            return
        spec = spec.validate()
        engine: Engine = get_engine(spec.engine)
        policy = spec.policy()
        build = engine.build_step(spec, self.cfg, self.opt, policy)
        if self.mesh is None:
            step_fn = jitted = jax.jit(build)
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from repro.launch import sharding as sh

            mesh = self.mesh
            pstruct, ostruct = self._state_struct(spec)
            pshard = sh.named(mesh, sh.param_specs(self.cfg, pstruct, mesh))
            oshard = sh.named(mesh, sh.opt_specs(self.cfg, ostruct, mesh))
            bspec = sh.batch_spec(mesh, spec.batch)
            bdim = bspec[0] if len(bspec) else None
            # pytree prefix: every batch leaf shards its leading (batch) dim
            bshard = NamedSharding(mesh, P(bdim))
            def packed(params, opt_state, batch, _b=build):
                # the loss and whatever the step returns after it (a
                # model's counters) as one output, so that one replicated
                # sharding covers them whatever their number
                params, opt_state, *rest = _b(params, opt_state, batch)
                return params, opt_state, tuple(rest)

            jitted = jax.jit(
                packed, in_shardings=(pshard, oshard, bshard),
                out_shardings=(pshard, oshard, NamedSharding(mesh, P())))
            self._state_shardings = (pshard, oshard)

            def step_fn(params, opt_state, batch, _j=jitted, _m=mesh):
                with _m:
                    params, opt_state, rest = _j(params, opt_state, batch)
                return (params, opt_state, *rest)

        self.engine, self.policy, self.step_fn = engine, policy, step_fn
        #: the raw jitted step (no mesh-context wrapper) — ``.lower()`` this
        #: for compiled-HLO inspection (fleet collective-bytes checks)
        self._jit_step = jitted
        self._compiled = None
        self._live_spec = spec

    @property
    def live_spec(self) -> TrainSpec:
        """The spec currently compiled (post-degradation, if any)."""
        return self._live_spec or self.spec

    def batch_struct(self):
        """ShapeDtypeStructs of one batch as :meth:`make_data` yields it for
        the live spec."""
        live = self.live_spec
        s = jax.ShapeDtypeStruct((live.batch, live.seq), jnp.int32)
        return {"tokens": s, "labels": s}

    def compile_step(self):
        """Compile the live spec's step for its state and batch shapes and
        return the ``Compiled`` program (kept until the next spec switch).
        The first real step then reuses this executable, and a compile
        error — never transient — raises here instead of inside the
        resilient loop's retry and OOM handling."""
        if self._compiled is None:
            pstruct, ostruct = self._state_struct(self.live_spec)
            if self.mesh is not None:
                # placed as init_state places the state and as the step
                # returns it, so no step call traces the step again
                def put(s, sharding):
                    return jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                sharding=sharding)

                pshard, oshard = self._state_shardings
                pstruct = jax.tree_util.tree_map(put, pstruct, pshard)
                ostruct = jax.tree_util.tree_map(put, ostruct, oshard)
            with (self.mesh if self.mesh is not None
                  else contextlib.nullcontext()):
                self._compiled = self._jit_step.lower(
                    pstruct, ostruct, self.batch_struct()).compile()
        return self._compiled

    # ---------------------------------------------------------------- state
    def init_state(self):
        from repro.models import model as model_lib

        live = self.live_spec
        params = model_lib.init_params(
            jax.random.PRNGKey(self.spec.seed), self.cfg,
            quantize=live.quantize)
        return self.shard_state(params, self.opt.init(params))

    def make_data(self, state=None):
        from repro.data import make_batch_iterator

        live = self.live_spec
        return make_batch_iterator(
            self.cfg.vocab, live.seq, live.batch,
            host_index=jax.process_index(), host_count=jax.process_count(),
            seed=self.spec.seed, state=state)

    # ------------------------------------------------------------------ fit
    def fit(self, steps: Optional[int] = None, *,
            data=None, on_step: Optional[Callable] = None,
            straggler=None, telemetry=None) -> TrainResult:
        """Run ``steps`` (default: spec.steps) supervised resilient training
        steps, resuming from the latest checkpoint in ``spec.ckpt_dir`` if
        any. Fault injection, the degradation ladder and the step guard are
        all driven by the spec's resilience fields; observability by the
        spec's telemetry fields (or an explicitly passed ``telemetry``)."""
        from repro import telemetry as tele
        from repro.checkpoint import Checkpointer
        from repro.data.pipeline import DataState, TokenStream
        from repro.runtime import degrade as degrade_mod
        from repro.runtime import faults as faults_mod
        from repro.runtime.fault_tolerance import ResilientLoop, \
            StragglerPolicy
        from repro.runtime.guard import StepGuard

        spec0 = self.spec
        total = steps if steps is not None else spec0.steps
        self._switch_to(spec0)
        try:
            self.compile_step()
        except Exception as e:
            # a compile error raises here, except an OOM with the ladder on:
            # the first step then raises it again inside the loop, which
            # hands it to the ladder (on_oom)
            if spec0.degrade != "on" or not faults_mod.is_oom_error(e):
                raise
            log.warning("step does not fit at compile: %s", e)
        ckpt = Checkpointer(spec0.ckpt_dir, interval=spec0.ckpt_interval)

        tel = telemetry if telemetry is not None \
            else tele.Telemetry.from_spec(spec0)
        injector = None
        if spec0.inject_faults:
            plan = faults_mod.FaultPlan.from_string(
                spec0.inject_faults, total_steps=total, seed=spec0.seed)
            injector = faults_mod.FaultInjector(plan,
                                               ckpt_dir=spec0.ckpt_dir)
            log.warning("chaos run: injecting faults [%s]", plan.to_string())
            if tel.enabled:
                injector.on_fire = lambda step, kind: tel.emit(
                    tele.FaultEvent(step=step, fault=kind, injected=True,
                                    source="injector"))
        guard = (StepGuard(budget=spec0.guard_budget,
                           telemetry=tel if tel.enabled else None)
                 if spec0.guard == "on" else None)
        ladder = (degrade_mod.DegradationLadder()
                  if spec0.degrade == "on" else None)
        straggler = straggler or StragglerPolicy(
            factor=spec0.straggler_factor,
            consecutive_limit=spec0.straggler_limit)
        # watermark monitor: on for telemetry runs, and whenever a memory
        # budget asks for proactive (pre-OOM) pressure handling
        memwatch = (tele.MemoryWatermark()
                    if tel.enabled or spec0.mem_budget_mb > 0 else None)
        if memwatch is not None:
            memwatch.predicted_mb = degrade_mod.predicted_peak_mb(
                self.live_spec) or 0.0
        pressure = (degrade_mod.WatermarkTrigger(spec0.mem_budget_mb)
                    if spec0.mem_budget_mb > 0 and ladder is not None
                    else None)

        def _log_step(res):
            tele.log_step(res, spec0.log_interval, quiet=spec0.quiet)
            if on_step:
                on_step(res)

        def extra_fn():
            return {"spec": _spec_manifest(self.live_spec)}

        def _sync_iter(loop, state):
            """Point the loop at an iterator matching the live spec's
            (seq, batch) positioned at ``state``."""
            live = self.live_spec
            if data is None:
                loop.batch_iter = self.make_data(state=state)
                return
            it = loop.batch_iter
            if state is not None:
                it.state = state
            elif loop._initial_data_state is not None:
                it.state = dataclasses.replace(loop._initial_data_state)
            if isinstance(it, TokenStream) and (it.seq_len != live.seq
                                                or it.batch != live.batch):
                loop.batch_iter = TokenStream(it.tokens, live.seq,
                                              live.batch, state=it.state)

        def restore_fn(loop):
            def template_fn(extra):
                saved = (extra or {}).get("spec")
                target = (dataclasses.replace(spec0, **saved) if saved
                          else spec0)
                self._switch_to(target)
                return self.init_state()

            try:
                restored = ckpt.restore_latest(template_fn=template_fn)
            except IOError as e:
                # every checkpoint corrupt: restart from step 0 rather
                # than lose the job (counters record the quarantines)
                log.error("all checkpoints unrestorable (%s); "
                          "restarting from scratch", e)
                restored = None
            if restored is None:
                self._switch_to(spec0)
                params, opt_state = self.init_state()
                _sync_iter(loop, None)
                loop.step_fn = self.step_fn
                return 0, params, opt_state
            log.info("resuming from step %d (engine=%s batch=%d seq=%d "
                     "quantize=%s)", restored["step"], self.live_spec.engine,
                     self.live_spec.batch, self.live_spec.seq,
                     self.live_spec.quantize)
            state = (DataState.from_dict(restored["data_state"])
                     if restored["data_state"] else None)
            _sync_iter(loop, state)
            loop.step_fn = self.step_fn
            return restored["step"], restored["params"], restored["opt_state"]

        def on_oom(loop):
            if ladder is None:
                return None
            live = self.live_spec
            try:
                cands = list(ladder.candidates(live))
            except degrade_mod.LadderExhausted as e:
                log.error("OOM with no rung left: %s", e)
                return None
            for cand, rung in cands:
                new_it = loop.batch_iter
                if cand.batch != live.batch or cand.seq != live.seq:
                    if not isinstance(new_it, TokenStream):
                        continue    # can't re-window an opaque iterator
                    new_it = TokenStream(new_it.tokens, cand.seq, cand.batch,
                                         state=new_it.state)
                try:
                    self._switch_to(cand)
                except (ValueError, KeyError) as e:   # engine refuses it
                    log.debug("rung %s unbuildable: %s", rung, e)
                    continue
                try:
                    self.compile_step()
                except Exception as e:
                    self._switch_to(live)
                    # a rung that does not fit either is skipped; any other
                    # compile error is a fault of the program, not pressure
                    if not faults_mod.is_oom_error(e):
                        raise
                    log.warning("rung %s does not fit at compile: %s",
                                rung, e)
                    continue
                params, opt_state = loop.params, loop.opt_state
                if cand.quantize != live.quantize:
                    from repro.core.quant import quantize_params
                    new_params = quantize_params(params, cand.quantize)
                    opt_state = degrade_mod.carry_opt_state(
                        opt_state, params, new_params)
                    params = new_params
                loop.batch_iter = new_it
                loop.step_fn = self.step_fn
                ladder.record(rung)
                pred = degrade_mod.predicted_peak_mb(cand)
                if memwatch is not None:
                    memwatch.predicted_mb = pred or 0.0
                if tel.enabled:
                    tel.emit(tele.DegradeEvent(
                        step=loop.step, rung=rung,
                        trigger=loop.degrade_trigger, engine=cand.engine,
                        quantize=cand.quantize, batch=cand.batch,
                        seq_len=cand.seq, predicted_peak_mb=pred or 0.0))
                    tel.registry.counter("degrade.rungs").inc()
                log.warning(
                    "memory pressure: degraded via %r -> engine=%s batch=%d "
                    "seq=%d quantize=%s (predicted peak %.0f MB)",
                    rung, cand.engine, cand.batch, cand.seq, cand.quantize,
                    pred or float("nan"))
                return params, opt_state
            return None

        it = data if data is not None else self.make_data()
        loop = ResilientLoop(
            self.step_fn, self.init_state, it, ckpt, total,
            max_retries=spec0.max_retries,
            restart_budget=8,    # supervised straggler restarts per run
            straggler=straggler, guard=guard, injector=injector,
            on_step=_log_step, on_oom=on_oom, restore_fn=restore_fn,
            extra_fn=extra_fn, telemetry=tel, memwatch=memwatch,
            pressure=pressure, train_counters=self.counters,
            aux_counters=self.moe_counters)
        if tel.enabled:
            tel.emit(tele.RunEvent(
                phase="start", engine=spec0.engine, quantize=spec0.quantize,
                arch=spec0.arch, spec=_spec_manifest(spec0)))
        try:
            params, opt_state, history, counters = loop.run()
            if tel.enabled:
                tel.emit(tele.RunEvent(
                    phase="end", engine=self.live_spec.engine,
                    quantize=self.live_spec.quantize, arch=spec0.arch,
                    steps=len(history),
                    final_loss=float(history[-1].loss) if history else None))
        finally:
            if telemetry is None:   # fit owns the lifecycle it created
                tel.close()
        metrics: dict = {}
        if guard is not None:
            metrics["guard"] = guard.state()
        if memwatch is not None:
            metrics["watermark"] = memwatch.compare()
        if tel.enabled:
            metrics["registry"] = tel.registry.snapshot()
            metrics["events_by_kind"] = tel.counts_by_kind()
            metrics["spans"] = tel.tracer.totals()
            metrics["telemetry_dir"] = tel.out_dir
        return TrainResult(
            params=params, opt_state=opt_state, history=history,
            counters=counters, final_spec=self.live_spec,
            degradations=list(ladder.applied) if ladder else [],
            metrics=metrics)
