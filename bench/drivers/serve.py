"""Serving cells: multi-tenant adapter serving through
``ContinuousBatcher.submit`` / ``.step``, fed open-loop.

Set-up makes the base and every tenant's adapter from ``--seed``, builds
the adapter store and the batcher, and runs two short requests through
``run`` so that the decode step and the loop's own eager operations are
compiled. The arrival schedule (``bench/arrivals.py``) then starts; the
first ``warm_seconds`` of it bring the batch to its steady state, and the
window measures the ``seconds`` after that. The end-to-end metric is the
tokens the batcher processed in the window (prompt tokens fed and tokens
generated) per second of it; time to first token and between tokens are
readings, each request timed from the moment it was due. Greedy decoding
throughout, so every served token can be checked against the reference's
logits.
"""
from __future__ import annotations

import time

import numpy as np

from bench import arrivals, device, weights
from bench import spec as spec_mod
from bench import trace as trace_mod


def _p95(xs):
    return float(np.percentile(np.asarray(xs, np.float64), 95)) if xs \
        else float("nan")


def run(*, conf, traffic, seed, seconds, trace_dir=None, fault=None,
        t_start, rate=None, root=spec_mod.ROOT):
    import jax
    import jax.numpy as jnp

    from repro.api import TrainSpec
    from repro.serve import AdapterStore, ContinuousBatcher, Request

    arch = spec_mod.load_arch(conf, root)
    w = arch.Widths.from_config(conf)
    cfg = spec_mod.arch_config(conf, root)
    traffic = dict(traffic, rate_per_s=rate or traffic["rate_per_s"])
    policy = TrainSpec(arch=conf["arch"], engine=traffic["engine"]).policy()
    device.refuse_interpret(policy)

    key = weights.root_key(seed)
    base = arch.make_base(w, key, cfg.dtype)
    params = arch.to_program(
        base, arch.make_lora(w, jax.random.fold_in(key, 1), cfg.dtype), w)
    b_std = traffic["adapter_b_std"]
    tenant_key = lambda t: jax.random.fold_in(key, 100 + t)  # noqa: E731
    store = AdapterStore(params, capacity=traffic["store_capacity"])
    bat = ContinuousBatcher(cfg, store, slots=traffic["slots"],
                            tile=traffic["tile"], max_len=traffic["max_len"],
                            page_size=traffic["page_size"], policy=policy)
    for t in range(traffic["tenants"]):
        bat.register_adapter(f"t{t}", arch.lora_tree(arch.make_lora(
            w, tenant_key(t), cfg.dtype, b_std)))
    del params

    toks = jnp.zeros((bat.slots, 1), jnp.int32)
    hlo = bat._jstep.lower(store.params, bat.cache, toks,
                           jnp.asarray(bat.tile_gid)).compile().as_text()
    device.require_kernels(hlo, traffic["kernels"])
    module = device.module_name(hlo)
    rng = np.random.default_rng(seed)
    bat.run([Request(f"warm{i}", f"t{i}", tuple(int(x) for x in
                     rng.integers(1, cfg.vocab, 4)), 2) for i in range(2)])

    window = min(seconds, traffic["trace_seconds"]) if trace_dir else seconds
    warm = traffic["warm_seconds"]
    sched = arrivals.schedule(traffic, warm + window + 5, seed, cfg.vocab)
    due, seen, last = {}, {}, {}
    ttft, tbt, late = [], [], []
    compiles = device.CompileCounter()
    pending = list(reversed(sched))
    t0 = time.perf_counter()
    t_open, t_close = t0 + warm, t0 + warm + window
    opened = False
    counters0 = None

    def observe(rid, n, now, in_window):
        had = seen.get(rid, 0)
        if n <= had:
            return
        if had == 0:
            if in_window:
                ttft.append(now - due[rid])
        elif in_window:
            tbt.append(now - last[rid])
            tbt.extend([0.0] * (n - had - 1))
        seen[rid], last[rid] = n, now

    while True:
        now = time.perf_counter()
        if not opened and now >= t_open:
            opened, t_opened = True, now
            setup_s = time.time() - t_start
            counters0 = dict(bat.counters)
            submitted0 = len(due)
            compiles.active = True
            if trace_dir:
                tctx = trace_mod.capture(trace_dir)
                tctx.__enter__()
        if opened and now >= t_close:
            t_end = now
            break
        while pending and t0 + pending[-1].due <= now:
            a = pending.pop()
            rid = f"r{len(due)}"
            due[rid] = t0 + a.due
            late.append(now - due[rid])
            bat.submit(Request(rid, f"t{a.tenant}", a.prompt, a.max_new))
        before = [r.req.rid for r in bat._rows if r.req is not None]
        with jax.profiler.TraceAnnotation(trace_mod.DISPATCH):
            busy = bat.step()
        if not busy:
            if pending:
                time.sleep(max(0.0, min(t0 + pending[-1].due, t_close)
                               - time.perf_counter()))
            continue
        now = time.perf_counter()
        in_window = opened
        for row in bat._rows:
            if row.req is not None:
                observe(row.req.rid, len(row.out), now, in_window)
        for rid in before:
            if rid in bat.results:
                observe(rid, len(bat.results[rid]), now, in_window)
    compiles.active = False
    if trace_dir:
        tctx.__exit__(None, None, None)
    if compiles.count:
        raise device.RunFault(f"{compiles.count} compilations inside the "
                              f"measured window")
    if bat._jstep._cache_size() != 1:
        raise device.RunFault(f"decode step traced "
                              f"{bat._jstep._cache_size()} times")
    counters = {k: bat.counters[k] - counters0[k] for k in counters0}
    elapsed = t_end - t_opened
    out = {
        "attempted": len(due) - submitted0,
        "failed": 0,
        "e2e": {"serve_tokens_per_s": (counters["prefill_tokens"]
                                       + counters["decoded_tokens"])
                / elapsed,
                "setup_s": setup_s},
        "memory_peak_bytes": device.peak_bytes(),
        "module": module,
        "counters": counters,
        "readings": {"ttft_ms_p95": 1e3 * _p95(ttft),
                     "tbt_ms_p95": 1e3 * _p95(tbt),
                     "ttft_count": len(ttft), "tbt_count": len(tbt),
                     "ttft_ms_p50": 1e3 * float(np.median(ttft)) if ttft
                     else float("nan"),
                     "late_ms_mean": 1e3 * float(np.mean(late)),
                     "queued_at_close": len(bat.queue),
                     "active_at_close": bat.active,
                     "completed_per_s": counters["completed"] / elapsed},
    }
    out["e2e"]["peak_hbm_gb"] = out["memory_peak_bytes"] / 1e9

    done = {rid: list(toks) for rid, toks in bat.results.items()
            if rid in due}
    prompts = {f"r{i}": (a.tenant, a.prompt)
               for i, a in enumerate(sched[:len(due)])}
    del bat, store
    if fault == "token":
        rid = max(done, key=lambda r: len(done[r]))
        done[rid][0] = (done[rid][0] + 1) % cfg.vocab

    def verify(control=False):
        """Reference logits over a seeded sample of finished requests (the
        longest among them); the widest gap by which a served token's
        logit lies below the reference's best. With ``control``, the gap
        of the token that the int8 reference puts first instead."""
        sample = _sample(done, traffic["check"], seed)
        ref = arch.Reference(conf)
        base = arch.make_base(w, key, cfg.dtype)
        lower = arch.quantize_int8(base, jnp.float32) if control else None
        gaps = []
        for rid in sample:
            tenant, prompt = prompts[rid]
            served = done[rid]
            lo = arch.make_lora(w, tenant_key(tenant), cfg.dtype, b_std)
            seq = np.zeros(traffic["max_len"], np.int32)
            full = list(prompt) + served
            seq[:len(full)] = full
            pos = np.zeros(traffic["output"]["max"], np.int32)
            pos[:len(served)] = len(prompt) - 1 + np.arange(len(served))
            logits = np.asarray(ref.logits(base, lo, seq, pos))[:len(served)]
            if control:
                pick = np.asarray(ref.logits(lower, lo, seq, pos))[
                    :len(served)].argmax(-1)
            else:
                pick = np.asarray(served)
            gaps.extend(logits.max(-1) - logits[np.arange(len(served)), pick])
        return {"served_gap": float(max(gaps)),
                "checked_tokens": float(len(gaps))}

    out["verify"] = verify
    return out


def _sample(done: dict, spec: dict, seed: int) -> list:
    """Finished requests to check: the one that served most tokens, then
    others drawn from the seed until ``spec["tokens"]`` tokens or
    ``spec["requests"]`` requests."""
    if not done:
        raise device.RunFault("no request finished")
    rids = sorted(done, key=lambda r: (-len(done[r]), r))
    pick = [rids[0]]
    rest = list(np.random.default_rng(seed).permutation(rids[1:]))
    total = len(done[rids[0]])
    while rest and total < spec["tokens"] and len(pick) < spec["requests"]:
        rid = str(rest.pop())
        pick.append(rid)
        total += len(done[rid])
    return pick
