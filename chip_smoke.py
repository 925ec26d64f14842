#!/usr/bin/env python3
"""Smoke run of MeSP fine-tuning and multi-tenant serving on a TPU.

Drives the entry points a user calls (``TrainSpec`` ->
``Trainer.from_spec(spec).fit()``, ``ContinuousBatcher.run``) once at
Qwen2.5-0.5B's published widths: bf16, LoRA rank 8 on q/k/v/o/gate/up/down,
seq 256, batch 1 (the paper's Table 1 setting), random weights from seed 0.

    python chip_smoke.py             # phases (a)-(d) on one chip
    python chip_smoke.py --chips 4   # (a) on a 4-chip data-parallel mesh
                                     # against the same global batch on one
                                     # chip of that host

Phases:

(a) engine ``mesp_pallas`` (the Pallas kernels), 5 steps;
(b) engine ``mesp`` (the jnp path) on the same batches: losses match (a);
(c) ``mesp_pallas`` on an int8 base, 3 steps;
(d) 4 requests from 2 adapters through ``ContinuousBatcher`` (16 prompt
    tokens, 8 new each): every request's tokens equal decoding it alone.

Every training run has ``degrade="off"`` and ``guard="on"``: no OOM ladder,
retry or guard skip may hide a failure. The compiled step of each kernel
phase must contain the named Pallas kernels as ``tpu_custom_call``s.

Times printed here are from one smoke run, not a benchmark. The last line
of stdout is ``{"ok": true, "device": {...}}``; it is printed only when
every check passed. Without a TPU the script exits non-zero before any
phase.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import re
import shutil
import sys
import tempfile
import time
from collections import Counter

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen2.5-0.5b"
SEQ = 256

# Loss agreement between the kernel and jnp paths. Both run in bf16 but
# round at different points: a Pallas kernel accumulates a whole tile in f32
# and rounds its output once, XLA rounds at its own fusion boundaries. The
# loss is a mean of 256 per-token cross-entropies, which averages most of
# those roundings out: on a v5e the two paths differ by 8.6e-5 relative at
# most over the five steps. 1e-3 leaves a margin of about 12 over that
# reading. The weights are random, so every loss stays near ln(vocab)
# whatever attention does, and LoRA B starts at zero, so the LoRA path adds
# nothing to the first loss. A broken kernel therefore moves the loss by
# little: with the flash kernel's causal mask removed, the loss moved by
# 7.7e-3 relative (the reduced config in bf16 on the CPU, where the two
# paths agree to 6.9e-5), and a wrong backward kernel moves it only through
# later steps, below 1e-4 there. The LoRA B check below is the one that
# sees a broken backward.
LOSS_RTOL = 1e-3

# LoRA B leaves start at zero, so after a few SGD steps they hold the summed
# dB of both paths. Gradients go back through all 24 layers in bf16, so
# they agree less closely than the loss (2.0e-2 on a v5e, 1.3e-2 on the
# reduced config on the CPU). There, a log-sum-exp off by log 2 in the
# attention backward gave 0.44, a zero dx 0.94 and a non-causal mask 1.1.
LORA_B_RTOL = 1e-1

TRAIN_KERNELS = ("lora_fwd", "lora_dx", "lora_dab",
                 "flash_fwd", "flash_dq", "flash_dkv")
INT8_KERNELS = ("lora_q_fwd", "lora_q_dx", "lora_dab",
                "flash_fwd", "flash_dq", "flash_dkv")
SERVE_KERNELS = ("lora_grouped_fwd",)

_CUSTOM_CALL = re.compile(
    r"%([A-Za-z_0-9]+?)(?:\.\d+)? = [^\n]*custom_call_target="
    r"\"tpu_custom_call\"")


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(*parts) -> None:
    print(*parts, flush=True)


def kernel_counts(hlo_text: str) -> Counter:
    """Pallas kernels in a compiled program, by ``pallas_call`` name."""
    return Counter(_CUSTOM_CALL.findall(hlo_text))


def peak_bytes() -> list:
    """``peak_bytes_in_use`` of every device (process peak so far)."""
    import jax

    peaks = []
    for dev in jax.devices():
        stats = dev.memory_stats()
        check(stats is not None and "peak_bytes_in_use" in stats,
              f"{dev} memory_stats() gives no peak_bytes_in_use")
        peaks.append(int(stats["peak_bytes_in_use"]))
    return peaks


def steady_step_seconds(tr, params, opt_state, reps: int = 3) -> float:
    """Median wall-clock of ``reps`` further steps of the compiled step,
    each ended by ``block_until_ready`` (after one untimed step)."""
    import jax

    batch = next(tr.make_data())
    out = jax.block_until_ready(tr.step_fn(params, opt_state, batch))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(tr.step_fn(out[0], out[1], batch))
        times.append(time.perf_counter() - t0)
    return sorted(times)[reps // 2]


def lora_b(params) -> list:
    """The LoRA B leaves, as float32 host arrays, in tree order."""
    import jax
    import numpy as np

    return [np.asarray(leaf, np.float32)
            for path, leaf in jax.tree_util.tree_leaves_with_path(params)
            if jax.tree_util.keystr(path).endswith("['b']")]


def train(label: str, *, engine: str, steps: int, quantize: str = "none",
          batch: int = 1, mesh=None, kernels=()) -> dict:
    """One ``Trainer.fit`` run through the user entry point, checked."""
    import jax

    from repro.api import Trainer, TrainSpec
    from repro.kernels import ops
    from repro.models.model import split_params

    ckpt = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        spec = TrainSpec(arch=ARCH, engine=engine, quantize=quantize,
                         steps=steps, batch=batch, seq=SEQ, seed=0,
                         degrade="off", guard="on", ckpt_dir=ckpt,
                         quiet=True)
        tr = Trainer.from_spec(spec, mesh=mesh)
        check(tr.policy.interpret is None and not ops.pallas_interpret(),
              f"[{label}] Pallas interpret mode would run on the chip")
        t0 = time.perf_counter()
        hlo = tr.compile_step().as_text()
        compile_s = time.perf_counter() - t0
        counts = kernel_counts(hlo)
        missing = [k for k in kernels if counts[k] < 1]
        check(not missing, f"[{label}] compiled step lacks kernels "
                           f"{missing}: {dict(counts)}")
        if not kernels:
            check(not counts, f"[{label}] jnp path runs Pallas kernels "
                              f"{dict(counts)}")

        res = tr.fit()
        losses = [float(h.loss) for h in res.history]
        faults = {k: v for k, v in res.counters.to_dict().items() if v}
        check(len(losses) == steps, f"[{label}] {len(losses)} of {steps} "
                                    f"steps recorded")
        check(all(math.isfinite(x) for x in losses),
              f"[{label}] non-finite loss {losses}")
        check(not res.degradations,
              f"[{label}] degraded via {res.degradations}")
        check(not faults, f"[{label}] fault counters {faults}")
        check(res.final_spec.engine == engine,
              f"[{label}] ended on engine {res.final_spec.engine}")
        step_s = steady_step_seconds(tr, res.params, res.opt_state)
        # one trace for every step: state that came back in another dtype
        # or layout would retrace and recompile the step behind our back
        traces = tr._jit_step._cache_size()
        check(traces == 1, f"[{label}] step traced {traces} times")
        peaks = peak_bytes()
        mesh_shape = dict(tr.mesh.shape) if tr.mesh is not None else None
        say(f"[{label}] engine={engine} quantize={quantize} batch={batch} "
            f"seq={SEQ} mesh={mesh_shape}")
        say(f"[{label}]   losses {losses}")
        say(f"[{label}]   compile_s {compile_s:.3f}   steady_step_s "
            f"{step_s:.6f} (one smoke run, not a benchmark)")
        say(f"[{label}]   peak_bytes_in_use {peaks}")
        say(f"[{label}]   kernels {dict(sorted(counts.items()))}")
        trainable, _ = split_params(res.params)
        out = {"losses": losses, "lora_b": lora_b(res.params), "hlo": hlo,
               "mesh": mesh_shape, "trainable_bytes": sum(
                   x.nbytes for x in jax.tree_util.tree_leaves(trainable))}
        del tr, res
        gc.collect()
        jax.clear_caches()
        return out
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)


def compare(label: str, a: dict, b: dict) -> None:
    """Step losses within LOSS_RTOL and LoRA B within LORA_B_RTOL."""
    import numpy as np

    la, lb = np.asarray(a["losses"]), np.asarray(b["losses"])
    rel = float(np.max(np.abs(la - lb) / np.abs(lb)))
    num = sum(float(np.sum((x - y) ** 2))
              for x, y in zip(a["lora_b"], b["lora_b"]))
    den = sum(float(np.sum(y ** 2)) for y in b["lora_b"])
    rel_b = math.sqrt(num / den) if den > 0 else float("inf")
    say(f"[{label}] max relative loss difference {rel:.3e} "
        f"(limit {LOSS_RTOL}); LoRA B relative difference {rel_b:.3e} "
        f"(limit {LORA_B_RTOL})")
    check(rel <= LOSS_RTOL, f"[{label}] losses differ: {la} vs {lb}")
    check(rel_b <= LORA_B_RTOL, f"[{label}] LoRA B differs by {rel_b}")


def serve() -> None:
    """(d): 4 requests, 2 adapters, one batcher; each equals its solo run."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import TrainSpec
    from repro.configs import get_config
    from repro.models import model as model_lib
    from repro.serve import (AdapterStore, ContinuousBatcher, Request,
                             synthetic_adapters)

    cfg = get_config(ARCH)
    policy = TrainSpec(arch=ARCH, engine="mesp_pallas").policy()
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    store = AdapterStore(params, capacity=2)
    bat = ContinuousBatcher(cfg, store, slots=4, tile=2, max_len=32,
                            policy=policy)
    uids = ["tenant0", "tenant1"]
    for i, uid in enumerate(uids):
        bat.register_adapter(uid, synthetic_adapters(params, seed=i))
    rng = np.random.default_rng(0)
    reqs = [Request(f"r{i}", uids[i % 2],
                    tuple(int(t) for t in rng.integers(1, cfg.vocab, 16)),
                    8) for i in range(4)]

    t0 = time.perf_counter()
    results = bat.run(reqs)
    together = {r.rid: list(results[r.rid]) for r in reqs}
    jax.block_until_ready(bat.cache)
    wall = time.perf_counter() - t0
    steps = bat.counters["steps"]
    toks = jnp.zeros((bat.slots, 1), jnp.int32)
    counts = kernel_counts(bat._jstep.lower(
        store.params, bat.cache, toks,
        jnp.asarray(bat.tile_gid)).compile().as_text())
    missing = [k for k in SERVE_KERNELS if counts[k] < 1]
    check(not missing, f"[d] decode step lacks kernels {missing}: "
                       f"{dict(counts)}")
    # prefill-as-decode: the last prompt step yields the first new token
    check(steps == 16 + 8 - 1, f"[d] 4 requests took {steps} batched "
                               f"steps, expected 23 (all four together)")
    for r in reqs:
        solo = bat.run([Request(r.rid + "/alone", r.adapter, r.prompt,
                                r.max_new)])[r.rid + "/alone"]
        check(list(solo) == together[r.rid],
              f"[d] {r.rid}: batched {together[r.rid]} != alone {solo}")
        check(len(solo) == r.max_new, f"[d] {r.rid}: {len(solo)} tokens")
    say(f"[d] serve arch={ARCH} adapters=2 requests=4 prompt=16 new=8 "
        f"slots=4 tile=2")
    say(f"[d]   tokens {together}")
    say(f"[d]   batched steps {steps}; wall_s {wall:.3f} incl. compile "
        f"(one smoke run, not a benchmark); each request equals its "
        f"solo decode")
    say(f"[d]   peak_bytes_in_use {peak_bytes()}")
    say(f"[d]   kernels {dict(sorted(counts.items()))}")


def one_chip() -> None:
    a = train("a", engine="mesp_pallas", steps=5, kernels=TRAIN_KERNELS)
    b = train("b", engine="mesp", steps=5)
    compare("a vs b", a, b)
    train("c", engine="mesp_pallas", steps=3, quantize="int8",
          kernels=INT8_KERNELS)
    serve()


def four_chips() -> None:
    """(a) on a 4-chip data-parallel mesh (the Trainer's default mesh over
    every visible chip, model_parallel=1) against the same global batch on
    one chip of the host (a one-device mesh: ``mesh=None`` would itself
    span all four)."""
    import jax

    from repro.roofline.analysis import collective_bytes
    from repro.runtime.elastic import make_mesh_from_devices

    check(len(jax.devices()) == 4,
          f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    dp = train("a/4 chips", engine="mesp_pallas", steps=5, batch=4,
               kernels=TRAIN_KERNELS)
    check(dp["mesh"] == {"data": 4, "model": 1},
          f"[a/4 chips] mesh {dp['mesh']}")
    coll = collective_bytes(dp["hlo"])
    say(f"[a/4 chips]   compiled collective bytes {coll}; trainable LoRA "
        f"params {dp['trainable_bytes']} bytes")
    check(coll["all-reduce"] > 0, "[a/4 chips] no gradient all-reduce")
    one = train("a/1 chip", engine="mesp_pallas", steps=5, batch=4,
                mesh=make_mesh_from_devices(jax.devices()[:1], 1),
                kernels=TRAIN_KERNELS)
    compare("4 chips vs 1 chip", dp, one)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the data-parallel phase and its "
                         "one-chip comparison")
    ap.add_argument("--pallas-interpret", default="auto",
                    choices=("auto", "on", "off"),
                    help="refused unless auto/off: the smoke run exists to "
                         "run the compiled kernels")
    args = ap.parse_args(argv)
    if args.pallas_interpret == "on":
        print("chip_smoke: refusing --pallas-interpret on (kernels must "
              "compile for the chip)", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend "
              f"{jax.default_backend()!r}); nothing was run",
              file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    say(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"jax {jax.__version__}; compile cache {cache}")
    t0 = time.perf_counter()
    try:
        four_chips() if args.chips == 4 else one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL {e}", file=sys.stderr)
        return 1
    say(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
