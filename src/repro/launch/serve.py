"""Serving launcher: a thin CLI over ``repro.serve``.

Two modes, picked by the model family:

* **Continuous batching** (dense/vlm): an :class:`~repro.serve.AdapterStore`
  holds ``--store-capacity`` resident tenants, requests round-robin over
  ``--adapters`` synthetic tenant adapters, and the
  :class:`~repro.serve.ContinuousBatcher` admits/recycles at step
  granularity with paged-KV accounting (tentpole path: grouped LoRA kernel
  under ``--engine mesp_pallas``).
* **Single-stream decode** (ssm/hybrid/audio/moe — no per-slot cache): the
  historical batched loop, one shared position for the whole batch.

Like ``launch/train.py``, the CLI is the registry-generated
:func:`repro.api.build_arg_parser` plus serve-only flags: the invocation is
a declarative :class:`repro.api.TrainSpec`, validated up front, and the
spec's :class:`~repro.api.ExecutionPolicy` is threaded through
``decode_step`` — so ``--quantize int8|int4|nf4`` serves against quantized
frozen weights (admission accounting follows via
``core/quant.weights_format``) and kernel/interpret overrides apply exactly
as in training.

Throughput discipline: a warmup pass is synced and *discarded* before the
timed region (compile + first-dispatch cost would otherwise deflate
steady-state tokens/s — same fix as the autotuner's timing loop).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-0.5b \\
        --reduced --adapters 4 --steps 32
"""
from __future__ import annotations

import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import ExecutionPolicy, TrainSpec, build_arg_parser
from repro.configs import get_config
from repro.core import quant
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as model_lib
from repro.serve import (AdapterStore, ContinuousBatcher, Request,
                         synthetic_adapters)

log = logging.getLogger("repro.serve")


class DecodeServer:
    """Single-stream batched decode (families without per-slot caches)."""

    def __init__(self, cfg, params, batch: int, max_len: int,
                 policy: ExecutionPolicy | None = None):
        self.cfg = cfg
        self.params = params
        self.batch = batch
        self.policy = policy if policy is not None else ExecutionPolicy()
        self.cache = model_lib.init_cache(cfg, batch, max_len)
        if cfg.family == "audio":
            self.cache["enc_out"] = jnp.zeros(
                (batch, cfg.encdec.encoder_seq, cfg.d_model),
                jnp.dtype(cfg.dtype))
        self._step = jax.jit(
            lambda p, c, t: model_lib.decode_step(p, cfg, c, t,
                                                  policy=self.policy))

    def step(self, tokens: jnp.ndarray) -> jnp.ndarray:
        """tokens [B,1] -> sampled next tokens [B,1] (greedy)."""
        logits, self.cache = self._step(self.params, self.cache, tokens)
        return jnp.argmax(logits, -1).astype(jnp.int32)


def _single_stream(cfg, params, spec, ns, policy) -> int:
    server = DecodeServer(cfg, params, spec.batch, ns.max_len, policy=policy)
    tok = jnp.ones((spec.batch, 1), jnp.int32)
    # warmup: compile + first dispatch, synced and discarded (not timed)
    tok = server.step(tok)
    jax.block_until_ready(tok)
    t0 = time.monotonic()
    outs = []
    for _ in range(spec.steps):
        tok = server.step(tok)
        outs.append(np.asarray(tok)[:, 0])
    dt = time.monotonic() - t0
    log.info("decoded %d steps × %d seqs in %.3fs (%.1f tok/s steady-state)",
             spec.steps, spec.batch, dt, spec.steps * spec.batch / dt)
    log.info("sample: %s", [int(x) for x in outs[-1]])
    return 0


def _request_trace(n: int, adapters: list, prompt_len: int,
                   max_new: int) -> list:
    return [Request(f"r{i}", adapters[i % len(adapters)],
                    tuple(1 + (i + j) % 97 for j in range(prompt_len)),
                    max_new)
            for i in range(n)]


def _continuous(cfg, params, spec, ns, policy) -> int:
    store = AdapterStore(params, capacity=ns.store_capacity)
    bat = ContinuousBatcher(cfg, store, slots=spec.batch, tile=ns.tile,
                            max_len=ns.max_len, page_size=ns.page_size,
                            policy=policy, mem_budget_mb=ns.mem_budget_mb,
                            weights_fmt=quant.weights_format(spec.quantize))
    uids = [f"tenant{i}" for i in range(ns.adapters)]
    for i, uid in enumerate(uids):
        bat.register_adapter(uid, synthetic_adapters(params, spec.seed + i))

    # warmup: one request end-to-end, synced and discarded — compiles the
    # decode step so the timed trace measures steady-state serving
    bat.run([Request("warmup", uids[0], (1, 2, 3), 2)])
    for c in (bat.counters, store.counters, bat.alloc.counters):
        c.update({k: 0 for k in c})
    bat.results.clear()

    reqs = _request_trace(ns.requests, uids, ns.prompt_len, ns.max_new)
    t0 = time.monotonic()
    results = bat.run(reqs)
    jax.block_until_ready(bat.cache)
    dt = time.monotonic() - t0
    served = sum(len(v) for v in results.values())
    log.info("served %d requests / %d tokens across %d tenants in %.3fs "
             "(%.1f tok/s)", len(results), served, ns.adapters, dt,
             served / dt)
    log.info("batcher: %s", bat.counters)
    log.info("store:   %s (resident %d/%d, %.2f MB/slot)", store.counters,
             store.resident, store.capacity, store.slot_bytes / 2**20)
    log.info("pages:   %s (%d/%d used)", bat.alloc.counters,
             bat.alloc.used_pages, bat.alloc.n_pages)
    return 0


def main(argv=None):
    ap = build_arg_parser()
    ap.prog = "repro.launch.serve"
    # serve's historical defaults (32 decode steps × 4 sequences), not
    # TrainSpec's training defaults — bare invocations stay comparable with
    # pre-migration tok/s logs
    ap.set_defaults(batch=4, steps=32)
    ap.add_argument("--max-len", type=int, default=128,
                    help="serve-only: decode cache capacity per slot")
    ap.add_argument("--adapters", type=int, default=1,
                    help="serve-only: synthetic tenant adapters to serve")
    ap.add_argument("--store-capacity", type=int, default=None,
                    help="serve-only: resident adapter slots "
                         "(default: min(adapters, 4))")
    ap.add_argument("--tile", type=int, default=None,
                    help="serve-only: decode rows per adapter tile "
                         "(default: batch // 2, min 1)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="serve-only: KV tokens per allocator page")
    ap.add_argument("--requests", type=int, default=None,
                    help="serve-only: request-trace length "
                         "(default: 2 × adapters)")
    ap.add_argument("--prompt-len", type=int, default=4,
                    help="serve-only: synthetic prompt tokens per request")
    ap.add_argument("--max-new", type=int, default=None,
                    help="serve-only: tokens generated per request "
                         "(default: --steps)")
    ap.add_argument("--mem-budget-mb", type=float, default=None,
                    help="serve-only: admission headroom budget against "
                         "benchmarks/memsim.serve_residency")
    ns = ap.parse_args(argv)
    spec = TrainSpec.from_namespace(ns).validate()
    logging.basicConfig(level=logging.INFO)
    enable_compile_cache()

    cfg = get_config(spec.arch)
    if spec.reduced:
        cfg = cfg.reduced()
    policy = spec.policy()
    params = model_lib.init_params(jax.random.PRNGKey(spec.seed), cfg,
                                   quantize=spec.quantize)
    log.info("arch=%s engine=%s quantize=%s backend=%s batch=%d adapters=%d",
             cfg.name, spec.engine, spec.quantize, policy.backend,
             spec.batch, ns.adapters)

    if cfg.family in ("dense", "vlm") and ns.adapters >= 1:
        if ns.store_capacity is None:
            ns.store_capacity = min(ns.adapters, 4)
        if ns.tile is None:
            ns.tile = max(spec.batch // 2, 1)
        if ns.requests is None:
            ns.requests = 2 * ns.adapters
        if ns.max_new is None:
            ns.max_new = spec.steps
        if ns.prompt_len + ns.max_new > ns.max_len:
            ap.error(f"--prompt-len + --max-new ({ns.prompt_len}+"
                     f"{ns.max_new}) exceeds --max-len {ns.max_len}")
        return _continuous(cfg, params, spec, ns, policy)
    if ns.adapters > 1:
        ap.error(f"--adapters > 1 needs a dense/vlm arch "
                 f"(got family {cfg.family!r})")
    return _single_stream(cfg, params, spec, ns, policy)


if __name__ == "__main__":
    raise SystemExit(main())
