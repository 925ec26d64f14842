"""Emulated-fleet launcher: real multi-device programs on one CPU host.

``--xla_force_host_platform_device_count=N`` makes a single CPU process
present N XLA devices, so the whole sharding stack — ``launch/sharding.py``
PartitionSpecs, the Trainer's sharded jit, ``runtime/elastic.py`` resizes,
HLO collectives — runs for real in CI, no accelerators required. XLA reads
the flag exactly once, when the backend initializes, so every fleet runs in
a **fresh subprocess** with the flag placed in its environment
(``xla_flags.force_host_device_count`` on an env *copy*); whatever JAX state
the parent process has is irrelevant.

Protocol: the parent writes a JSON payload (task + spec overrides), the
worker (``python -m repro.launch.fleet payload.json result.json``) runs it
and writes a JSON result; arrays travel via ``.npz`` side files (payload
``"out"``). Tasks:

* ``train`` — deterministic synthetic-batch training through the Trainer
  facade; returns losses + per-step wall times, dumps final state.
* ``collectives`` — compile the sharded step, parse collective payload
  bytes from the HLO (``roofline.analysis.collective_bytes``) and compare
  with the analytic prediction (``predicted_grad_sync_bytes``).
* ``elastic`` — live 8→4→8 resize through ``Trainer.resize`` vs the
  checkpoint-restore path vs an uninterrupted run, all inside the worker.

Used by tests/multihost/ (correctness) and benchmarks/scaling.py (the
step-time-vs-device-count curve).
"""
from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import tempfile
import traceback
from typing import Dict, List, Optional

from repro.launch.xla_flags import force_host_device_count

#: steps discarded from the front of every timing series (compile + warm-up)
WARMUP_STEPS = 1


# ---------------------------------------------------------------------------
# parent side
# ---------------------------------------------------------------------------


def fleet_env(devices: int, env: Optional[dict] = None) -> dict:
    """A subprocess environment presenting ``devices`` emulated CPU devices.
    Starts from (a copy of) the current environment: user XLA_FLAGS survive,
    only the device-count flag is replaced."""
    env = dict(os.environ if env is None else env)
    env["JAX_PLATFORMS"] = "cpu"
    force_host_device_count(devices, env=env)
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    return env


def run_fleet(payload: dict, *, devices: int, timeout: float = 1500.0) -> dict:
    """Run one worker task on an emulated ``devices``-device fleet and return
    its result dict. Raises RuntimeError (with the worker's stderr tail) on
    a non-zero exit or a worker-reported error."""
    with tempfile.TemporaryDirectory(prefix="repro_fleet_") as td:
        ppath = os.path.join(td, "payload.json")
        rpath = os.path.join(td, "result.json")
        with open(ppath, "w") as f:
            json.dump(payload, f)
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.fleet", ppath, rpath],
            env=fleet_env(devices), capture_output=True, text=True,
            timeout=timeout)
        if proc.returncode != 0 or not os.path.exists(rpath):
            raise RuntimeError(
                f"fleet worker ({devices} devices) failed rc="
                f"{proc.returncode}:\n{proc.stderr[-4000:]}")
        with open(rpath) as f:
            result = json.load(f)
    if result.get("status") != "ok":
        raise RuntimeError(
            f"fleet worker ({devices} devices) errored:\n"
            f"{result.get('error')}\n{result.get('traceback', '')[-4000:]}")
    return result


def merge_fleet_telemetry(telemetry_dir: str,
                          out_name: str = "fleet.jsonl") -> Optional[str]:
    """Merge per-worker ``worker_<id>.jsonl`` shards under ``telemetry_dir``
    into one deterministic timeline (sorted by ``(ts, worker, seq)`` — see
    ``repro.telemetry.events.merge_jsonl_shards``). Returns the merged path,
    or None when no shards exist. Byte-deterministic in the shard *set*, not
    the glob order, so re-merges and shuffled worker finishes agree."""
    from repro.telemetry.events import merge_jsonl_shards

    shards: List[str] = sorted(
        glob.glob(os.path.join(telemetry_dir, "worker_*.jsonl")))
    if not shards:
        return None
    out = os.path.join(telemetry_dir, out_name)
    merge_jsonl_shards(shards, out)
    return out


# ---------------------------------------------------------------------------
# worker side (fresh subprocess — jax imported lazily, after XLA_FLAGS took
# effect at backend init)
# ---------------------------------------------------------------------------


def synth_batch(vocab: int, batch: int, seq: int, seed: int, step: int) -> dict:
    """Deterministic synthetic batch — a pure function of (seed, step) and
    the *global* shape, so every device count sees identical data."""
    import numpy as np

    rng = np.random.default_rng((seed, step))
    toks = rng.integers(0, vocab, (batch, seq), dtype=np.int32)
    return {"tokens": toks, "labels": toks}


def _flat(tree, prefix: str) -> Dict[str, "object"]:
    """Flatten a pytree to {path-string: ndarray} for npz interchange.
    (None leaves — frozen slots — are not pytree leaves and drop out
    identically on every worker, so flat keys always line up.)"""
    import jax
    import numpy as np

    out = {}
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in leaves:
        key = prefix + "/" + "/".join(
            str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        out[key] = np.asarray(leaf)
    return out


def _mesh_axes(mesh) -> Dict[str, int]:
    if mesh is None:
        return {}
    return {a: int(mesh.shape[a]) for a in mesh.axis_names}


def _make_trainer(payload: dict):
    from repro.api.spec import TrainSpec
    from repro.api.trainer import Trainer

    spec = TrainSpec(**payload.get("spec", {}))
    return Trainer.from_spec(spec)


def _worker_telemetry(payload: dict):
    """Per-worker Telemetry writing a ``worker_<id>.jsonl`` shard when the
    payload carries ``telemetry_dir`` (parent merges shards afterwards with
    :func:`merge_fleet_telemetry`); the DISABLED singleton otherwise."""
    from repro import telemetry as tele

    tdir = payload.get("telemetry_dir")
    if not tdir:
        return tele.DISABLED
    os.makedirs(tdir, exist_ok=True)
    return tele.Telemetry(enabled=True, out_dir=tdir,
                          worker=int(payload.get("worker_id", 0)))


def task_train(payload: dict) -> dict:
    import time

    import jax
    import numpy as np

    from repro import telemetry as tele

    tr = _make_trainer(payload)
    params, opt_state = tr.init_state()
    params, opt_state = tr.shard_state(params, opt_state)
    spec = tr.live_spec
    tel = _worker_telemetry(payload)
    memwatch = tele.MemoryWatermark() if tel.enabled else None
    tel.emit(tele.RunEvent(phase="start", engine=spec.engine,
                           quantize=spec.quantize, arch=spec.arch,
                           steps=int(payload.get("steps", spec.steps))))
    losses, times, counters = [], [], {}
    try:
        for step in range(int(payload.get("steps", spec.steps))):
            batch = synth_batch(tr.cfg.vocab, spec.batch, spec.seq,
                                spec.seed, step)
            t0 = time.perf_counter()
            with tel.span("fleet/step"):
                params, opt_state, loss, *aux = jax.block_until_ready(
                    tr.step_fn(params, opt_state, batch))
            for k, v in (aux[0] if aux else {}).items():
                counters[k] = counters.get(k, 0) + int(v)
            dt = time.perf_counter() - t0
            times.append(dt)
            losses.append(float(loss))
            tel.emit(tele.StepEvent(step=step, loss=float(loss), seconds=dt))
            if memwatch is not None:
                m = memwatch.sample()
                tel.emit(tele.WatermarkEvent(
                    step=step, measured_mb=m["measured_mb"],
                    peak_mb=m["peak_mb"], source=m["source"]))
        tel.emit(tele.RunEvent(
            phase="end", steps=len(losses),
            final_loss=losses[-1] if losses else 0.0))
    finally:
        tel.close()
    if payload.get("out"):
        np.savez(payload["out"], **_flat(params, "params"),
                 **_flat(opt_state, "opt"))
    steady = times[WARMUP_STEPS:] or times
    result = {"losses": losses, "step_times_s": times,
              "step_time_s": float(np.median(steady)),
              "devices": jax.device_count(), "mesh": _mesh_axes(tr.mesh),
              "counters": counters}
    if tel.enabled and tel.out_dir:
        result["telemetry_shard"] = os.path.join(
            tel.out_dir, f"worker_{tel.worker}.jsonl")
    return result


def task_collectives(payload: dict) -> dict:
    import jax

    from repro.models.model import split_params
    from repro.roofline.analysis import (collective_bytes,
                                         predicted_grad_sync_bytes)

    tr = _make_trainer(payload)
    pstruct, _ = tr._state_struct(tr.live_spec)
    coll = collective_bytes(tr.compile_step().as_text())
    train, _ = split_params(pstruct)
    leaves = jax.tree_util.tree_leaves(train)
    n_trainable = sum(l.size for l in leaves)
    # Two subtleties in the analytic floor vs what HLO parsing can see:
    # (1) grads sync in the model's *compute* dtype (``cfg.dtype``) — params
    #     may be stored wider (f32 masters), but the all-reduce payload XLA
    #     emits is the gradient;
    # (2) the structured backward walks the L stacked blocks in a loop, so
    #     the compiled program contains ONE loop body whose all-reduces
    #     cover a single layer slice of the blocks' grads (executed L times
    #     at run time). Static HLO byte-parsing counts that body once, so
    #     the floor on *static* bytes is the per-layer slice of stacked
    #     leaves plus any non-stacked trainables in full.
    import jax.numpy as jnp
    item = jnp.dtype(tr.cfg.dtype).itemsize
    blk_ids = {id(l) for l in jax.tree_util.tree_leaves(
        train.get("blocks", {}) if isinstance(train, dict) else {})}
    static_elems = sum(l.size // l.shape[0] if id(l) in blk_ids else l.size
                       for l in leaves)
    trainable_bytes = n_trainable * item
    static_trainable_bytes = static_elems * item
    axes = _mesh_axes(tr.mesh)
    return {"collective_bytes": coll, "n_trainable": int(n_trainable),
            "trainable_bytes": int(trainable_bytes),
            "static_trainable_bytes": int(static_trainable_bytes),
            "predicted_grad_sync_bytes":
                predicted_grad_sync_bytes(static_trainable_bytes, axes,
                                          dtype_bytes=1),
            "devices": jax.device_count(), "mesh": axes}


def task_elastic(payload: dict) -> dict:
    """8→4→8 elastic resize, three ways, all inside this worker:

    * A — uninterrupted run on the full fleet (reference trajectory);
    * B — live resize through ``Trainer.resize`` at the phase boundaries;
    * C — checkpoint path: state round-trips through host numpy copies and
      fresh Trainer instances per mesh (what a real restore does).

    B and C execute the *same program sequence*, so they must be
    bit-identical — that is the elasticity contract. A runs a different
    XLA SPMD partitioning per device count, so A-vs-B agrees only to
    float tolerance (see docs/sharding.md)."""
    import jax
    import numpy as np

    from repro.api.trainer import Trainer
    from repro.api.spec import TrainSpec
    from repro.runtime.elastic import make_mesh_from_devices, reshard_tree
    from repro.launch import sharding as sh

    spec = TrainSpec(**payload.get("spec", {}))
    phases = payload.get("phases", [2, 2, 2])   # steps per mesh phase
    n_full = jax.device_count()
    n_small = int(payload.get("shrink_to", max(n_full // 2,
                                               spec.model_parallel)))
    mp = spec.model_parallel
    dev_full, dev_small = jax.devices(), jax.devices()[:n_small]

    def batches():
        step = 0
        while True:
            yield synth_batch(TrainerRef.cfg.vocab, spec.batch, spec.seq,
                              spec.seed, step)
            step += 1

    # --- A: uninterrupted on the full fleet
    TrainerRef = Trainer.from_spec(spec)
    params_a, opt_a = TrainerRef.shard_state(*TrainerRef.init_state())
    gen = batches()
    losses_a = []
    for _ in range(sum(phases)):
        params_a, opt_a, loss, *_ = TrainerRef.step_fn(params_a, opt_a,
                                                       next(gen))
        losses_a.append(float(loss))

    # --- reshard_tree round trip is placement-only (bit-exact)
    mesh_small = make_mesh_from_devices(dev_small, mp)
    moved = reshard_tree(params_a, mesh_small,
                         sh.param_specs(TrainerRef.cfg, params_a, mesh_small))
    back = reshard_tree(moved, TrainerRef.mesh,
                        sh.param_specs(TrainerRef.cfg, params_a,
                                       TrainerRef.mesh))
    reshard_bitexact = all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree_util.tree_leaves(params_a),
                        jax.tree_util.tree_leaves(back)))

    # --- B: live resize through the Trainer facade
    tr = Trainer.from_spec(spec)
    params_b, opt_b = tr.shard_state(*tr.init_state())
    gen = batches()
    losses_b = []
    plan = [(dev_full, phases[0]), (dev_small, phases[1]),
            (dev_full, phases[2])]
    for i, (devs, n) in enumerate(plan):
        if i > 0:
            params_b, opt_b = tr.resize(devs, params=params_b,
                                        opt_state=opt_b)
        for _ in range(n):
            params_b, opt_b, loss, *_ = tr.step_fn(params_b, opt_b, next(gen))
            losses_b.append(float(loss))

    # --- C: checkpoint-restore path (host round trip + fresh Trainer)
    def to_host(tree):
        return jax.tree_util.tree_map(lambda x: np.asarray(x), tree)

    gen = batches()
    losses_c = []
    state = None
    for i, (devs, n) in enumerate(plan):
        mesh = make_mesh_from_devices(list(devs), mp)
        trc = Trainer.from_spec(spec, mesh=mesh)
        if state is None:
            state = trc.init_state()
        params_c, opt_c = trc.shard_state(*state)
        for _ in range(n):
            params_c, opt_c, loss, *_ = trc.step_fn(params_c, opt_c, next(gen))
            losses_c.append(float(loss))
        state = (to_host(params_c), to_host(opt_c))

    leaves = lambda t: [np.asarray(x) for x in jax.tree_util.tree_leaves(t)]
    b_vs_c_bitwise = (
        losses_b == losses_c and
        all(np.array_equal(x, y) for x, y in zip(leaves(params_b),
                                                 leaves(state[0]))) and
        all(np.array_equal(x, y) for x, y in zip(leaves(opt_b),
                                                 leaves(state[1]))))
    b_vs_a_maxdiff = max(
        float(np.max(np.abs(x - y)))
        for x, y in zip(leaves(params_a), leaves(params_b)))
    return {"reshard_bitexact": bool(reshard_bitexact),
            "b_vs_c_bitwise": bool(b_vs_c_bitwise),
            "b_vs_a_maxdiff": b_vs_a_maxdiff,
            "losses_a": losses_a, "losses_b": losses_b,
            "losses_c": losses_c,
            "devices": n_full, "shrink_to": n_small}


def task_ladder(payload: dict) -> dict:
    """Sharding × resilience seam: every degradation-ladder rung reachable
    from the payload spec must *build, compile and run* a sharded step on
    the live model-parallel mesh — halved batches falling below the DP size
    (batch_spec replicates), int8's ``{"q","scale"}`` leaves (param_specs
    reuses the w layout), truncated seqs breaking Megatron-SP divisibility
    (act_spec recomputed per switch) all included."""
    import jax
    import numpy as np

    from repro.core.quant import quantize_params
    from repro.runtime import degrade as degrade_mod

    tr = _make_trainer(payload)
    base = tr.live_spec
    params0, opt0 = tr.shard_state(*tr.init_state())
    rungs = []
    for cand, rung in degrade_mod.DegradationLadder().candidates(base):
        try:
            tr._switch_to(cand)
        except Exception as e:   # unbuildable rung (Trainer skips these too)
            rungs.append({"rung": rung, "built": False,
                          "reason": f"{type(e).__name__}: {e}"})
            continue
        params, opt_state = params0, opt0
        if cand.quantize != base.quantize:
            new_params = quantize_params(params, cand.quantize)
            opt_state = degrade_mod.carry_opt_state(opt_state, params,
                                                    new_params)
            params = tr.shard_state(new_params)
        live = tr.live_spec
        batch = synth_batch(tr.cfg.vocab, live.batch, live.seq,
                            live.seed, 0)
        _, _, loss, *_ = tr.step_fn(params, opt_state, batch)
        rungs.append({"rung": rung, "built": True,
                      "loss": float(loss),
                      "finite": bool(np.isfinite(float(loss))),
                      "batch": live.batch, "seq": live.seq,
                      "engine": live.engine, "quantize": live.quantize})
        tr._switch_to(base)   # reset for the next rung
    return {"rungs": rungs, "devices": jax.device_count(),
            "mesh": _mesh_axes(tr.mesh)}


def task_probe(payload: dict) -> dict:
    """Topology-only: build a mesh on the emulated fleet and report its
    geometry (no model, no compile — cheap enough for edge-case tests)."""
    import jax

    from repro.runtime.elastic import make_mesh_from_devices

    mesh = make_mesh_from_devices(
        jax.devices(), payload.get("model_parallel", 1),
        pods=payload.get("pods", 1))
    return {"axis_names": list(mesh.axis_names), "mesh": _mesh_axes(mesh),
            "devices": jax.device_count()}


TASKS = {"train": task_train, "collectives": task_collectives,
         "elastic": task_elastic, "ladder": task_ladder,
         "probe": task_probe}


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    if len(argv) != 2:
        print("usage: python -m repro.launch.fleet payload.json result.json",
              file=sys.stderr)
        return 2
    with open(argv[0]) as f:
        payload = json.load(f)
    try:
        result = TASKS[payload.get("task", "train")](payload)
        result["status"] = "ok"
    except Exception as e:   # report through the JSON channel, not the rc
        result = {"status": "error",
                  "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()}
    with open(argv[1], "w") as f:
        json.dump(result, f, default=str)
    return 0


if __name__ == "__main__":
    sys.exit(main())
