"""Training cells: ``Trainer.from_spec(spec).fit()``, the user's entry,
driven through its host loop as a user runs it.

Set-up builds one Trainer, compiles its step (which must hold the named
Pallas kernels), and lets ``fit`` run ``WARM_STEPS`` steps on the
benchmark's weights and feed: the first ``CHECK_STEPS`` of them are
compared with the reference after the run, the rest warm whatever the
loop runs between steps. The window then opens at a step boundary and
closes at the first step boundary ``seconds`` later; the harness ends
``fit`` there by raising from ``on_step``, so no checkpoint is written
(the spec's interval is beyond any run). ``degrade="off"``: an OOM fails
the run instead of changing the program.
"""
from __future__ import annotations

import shutil
import tempfile
import time

import numpy as np

from bench import check, device, weights
from bench import spec as spec_mod
from bench import trace as trace_mod
from bench.data import Windows

CHECK_STEPS = 3
WARM_STEPS = 5
NEVER = 10 ** 9


class WindowClosed(Exception):
    pass


def _host(tree):
    return {t: {k: np.asarray(v, np.float32) for k, v in d.items()}
            for t, d in tree.items()}


def _half_batch(batch):
    """Fault: half the batch left out (labels -1 are not in the mean)."""
    lab = np.array(batch["labels"])
    if lab.shape[0] > 1:
        lab[lab.shape[0] // 2:] = -1
    else:
        lab[:, lab.shape[1] // 2:] = -1
    return {**batch, "labels": lab}


def run(*, conf, traffic, seed, seconds, trace_dir=None, quantize=None,
        fault=None, t_start, root=spec_mod.ROOT):
    """One run of a training cell. Returns the readings, and ``verify``,
    which runs the reference once the program's state is freed (on return
    from here) and gives the numbers compared."""
    import jax

    from repro.api import Trainer, TrainSpec
    from repro.core.quant import quantize_params
    from repro.models import model as model_lib

    arch = spec_mod.load_arch(conf, root)
    w = arch.Widths.from_config(conf)
    cfg = spec_mod.arch_config(conf, root)
    quantize = quantize or traffic["quantize"]
    batch, seq, lr = traffic["batch"], traffic["seq"], traffic["lr"]
    ckpt = tempfile.mkdtemp(prefix="bench_ckpt_")
    tspec = TrainSpec(
        arch=conf["arch"], engine=traffic["engine"], quantize=quantize,
        optimizer=traffic["optimizer"], lr=lr, steps=NEVER, batch=batch,
        seq=seq, seed=seed % (2 ** 31 - 1), ckpt_dir=ckpt,
        ckpt_interval=NEVER, log_interval=NEVER, degrade="off",
        guard=traffic["guard"], quiet=True)
    tr = Trainer.from_spec(tspec, cfg=cfg)
    device.refuse_interpret(tr.policy)

    key = weights.root_key(seed)
    base = arch.make_base(w, key, cfg.dtype)
    lora0 = arch.make_lora(w, jax.random.fold_in(key, 1), cfg.dtype)
    p0 = _host(lora0)
    params = arch.to_program(base, lora0, w)
    if quantize != "none":
        params = quantize_params(params, quantize)
    want = jax.eval_shape(lambda: model_lib.init_params(
        jax.random.PRNGKey(0), cfg, quantize=quantize))
    have = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    if jax.tree_util.tree_structure(have) != \
            jax.tree_util.tree_structure(want) or have != want:
        raise device.RunFault("benchmark weights do not match the "
                              "program's parameter tree")
    # fit's loop gets the state and the harness keeps no reference to it:
    # the step returns a new tree each step, so a kept initial state would
    # hold a second copy of the weights
    handover = [params, tr.opt.init(params)]
    del base, lora0, params

    def init_state():
        if not handover:
            raise device.RunFault("fit asked for its initial state twice "
                                  "(a retry or restore)")
        p, o = handover
        handover.clear()
        return tr.shard_state(p, o)

    tr.init_state = init_state

    hlo = tr.compile_step().as_text()
    kernels = traffic["kernels"][quantize]
    device.require_kernels(hlo, kernels)
    module = device.module_name(hlo)

    st = {"calls": 0, "steps": 0, "losses": [],
          "batches": [], "p1": None, "p3": None}
    compiles = device.CompileCounter()
    inner = tr.step_fn

    def step_fn(p, o, b):
        k = st["calls"]
        st["calls"] += 1
        if k < CHECK_STEPS:
            st["batches"].append({n: np.array(v) for n, v in b.items()})
        if fault == "half_batch":
            b = _half_batch(b)
        with jax.profiler.TraceAnnotation(trace_mod.DISPATCH):
            out = inner(p, o, b)
        if fault == "unchanged":
            out = (p, o, out[2])
        if k == 0:
            st["p1"] = _host(arch.lora_of(out[0]))
        elif k == CHECK_STEPS - 1:
            st["p3"] = _host(arch.lora_of(out[0]))
        return out

    tr.step_fn = step_fn
    window = min(seconds, traffic["trace_seconds"]) if trace_dir else seconds

    def on_step(res):
        now = time.perf_counter()
        st["steps"] += 1
        if st["steps"] != st["calls"] and st["steps"] <= WARM_STEPS:
            raise device.RunFault(f"step {st['steps']} was retried or "
                                  f"rejected before the window")
        if st["steps"] <= CHECK_STEPS:
            st["losses"].append(float(res.loss))
        if st["steps"] == WARM_STEPS:
            st["setup_s"] = time.time() - t_start
            st["calls0"] = st["calls"]
            compiles.active = True
            if trace_dir:
                st["trace"] = trace_mod.capture(trace_dir)
                st["trace"].__enter__()
            st["t_open"] = time.perf_counter()
        elif st["steps"] > WARM_STEPS and now - st["t_open"] >= window:
            st["t_close"] = now
            compiles.active = False
            if "trace" in st:
                st["trace"].__exit__(None, None, None)
            raise WindowClosed

    try:
        tr.fit(data=Windows(cfg.vocab, seq, batch, seed), on_step=on_step)
    except WindowClosed:
        pass
    else:
        raise device.RunFault("fit returned before the window closed")
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    if compiles.count:
        raise device.RunFault(f"{compiles.count} compilations inside the "
                              f"measured window")
    if tr._jit_step._cache_size() != 1:
        raise device.RunFault(f"step traced {tr._jit_step._cache_size()} "
                              f"times")
    n_steps = st["steps"] - WARM_STEPS
    elapsed = st["t_close"] - st["t_open"]
    out = {
        "attempted": n_steps,
        "failed": st["calls"] - st["calls0"] - n_steps,
        "e2e": {"train_tokens_per_s": n_steps * batch * seq / elapsed,
                "setup_s": st["setup_s"]},
        "memory_peak_bytes": device.peak_bytes(),
        "module": module,
        "window_s": elapsed,
    }
    out["e2e"]["peak_hbm_gb"] = out["memory_peak_bytes"] / 1e9

    batches, losses, p1, p3 = (st["batches"], st["losses"], st["p1"],
                               st["p3"])

    def verify(control=False):
        """The reference over the first steps' batches, once the program's
        state is gone; returns the numbers compared. With ``control``, the
        reference on an int8-rounded base stands in the program's place."""
        ref = arch.Reference(conf)
        base = arch.make_base(w, key, cfg.dtype)
        lora0 = arch.make_lora(w, jax.random.fold_in(key, 1), cfg.dtype)
        ref_losses, ref_grads, ref_states = ref.sgd(base, lora0, batches, lr)
        got_losses, got_p1, got_p3 = losses, p1, p3
        if control:
            lower = arch.quantize_int8(base)
            del base        # one copy of the weights beside the reference
            got_losses, _, states = ref.sgd(lower, lora0, batches, lr)
            got_p1, got_p3 = _host(states[0]), _host(states[-1])
        return check.train_numbers(
            lr=lr, losses=got_losses, ref_losses=ref_losses, p0=p0,
            p1=got_p1, p3=got_p3, ref_grads=[_host(g) for g in ref_grads],
            ref_p3=_host(ref_states[-1]))

    out["verify"] = verify
    return out
