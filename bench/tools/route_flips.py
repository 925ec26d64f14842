#!/usr/bin/env python3
"""Share of the expert choices that the program makes otherwise than the
float32 reference, on a cell's first checked batch.

    python bench/tools/route_flips.py --workload moonlight-16b-a3b.ft.s8192 \
        --seeds 3017000001,3017000002

For each seed: the cell's weights and first batch (as ``bench/run.py``
makes them, LoRA at its starting state), then layer by layer the program's
forward (the cell's engine, in the configuration's dtype) and the
reference's (float32, ``Precision.HIGHEST``), each from its own hidden
states. At every MoE layer it compares the top-k experts each token
chooses and prints, as one JSON line, the share of (token, slot) choices
that differ, by layer and over all. Near-tied scores flip under bf16
rounding; the share says how much of the correctness check's gap comes
from routing. Runs on the chip or, at small sizes, the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def flips(conf, traffic, seed: int, *, cfg=None) -> dict:
    import jax
    import jax.numpy as jnp

    from bench import spec, weights
    from bench.data import Windows
    from bench.reference import F32, mm, rmsnorm
    from repro.api.policy import ExecutionPolicy
    from repro.models import layers, model, moe

    arch = spec.load_arch(conf)
    w = arch.Widths.from_config(conf)
    cfg = cfg or spec.arch_config(conf)
    pol = ExecutionPolicy(backend="pallas" if traffic["engine"]
                          == "mesp_pallas" else "structured")
    key = weights.root_key(seed)
    base = arch.make_base(w, key, cfg.dtype)
    lora = arch.make_lora(w, jax.random.fold_in(key, 1), cfg.dtype)
    params = arch.to_program(base, lora, w)
    tokens = jnp.asarray(next(iter(Windows(cfg.vocab, traffic["seq"],
                                           traffic["batch"],
                                           seed)))["tokens"])
    eps = float(conf["published"]["rms_norm_eps"])
    theta = float(conf["published"]["rope_theta"])
    scale = float(conf["assumed"]["lora"]["alpha"]) / w.rank
    (l0, lo0), (rest, lo_rest) = arch._split_layers(base, lora, w)
    f32 = lambda t: jax.tree_util.tree_map(lambda a: a.astype(F32), t)

    @jax.jit
    def prog_first(tokens):
        x = layers.embed(params["embed"], tokens, cfg)
        return model.dense_block(params["block0"], x, cfg, policy=pol)[0]

    @jax.jit
    def prog_layer(x, bp):
        a, _ = model._attention(cfg)(
            bp["attn"], layers.norm(bp["ln1"], x, cfg, policy=pol), cfg,
            policy=pol)
        x = x + a
        h = layers.norm(bp["ln2"], x, cfg, policy=pol)
        _, idx = moe.route(bp["moe"], h, cfg)
        y, _ = moe.moe_mlp(bp["moe"], h, cfg, policy=pol)
        return x + y, idx

    @jax.jit
    def ref_first(tok):
        x = base["embed"][tok].astype(F32)
        return arch.dense_layer(x, f32(l0), f32(lo0), w, eps, theta, scale)

    @jax.jit
    def ref_layer(x, lw, lo):
        lw, lo = f32(lw), f32(lo)
        x1 = x + arch.attention(rmsnorm(x, lw["ln1"], eps), lw, lo, w, eps,
                                theta, scale)
        h = rmsnorm(x1, lw["ln2"], eps)
        scores = jax.nn.sigmoid(mm(h, lw["router_w"]))
        _, idx = jax.lax.top_k(scores + lw["score_bias"], w.top_k)
        return arch.moe_layer(x, lw, lo, w, eps, theta, scale), idx

    def differ(a, b):
        same = jnp.sum(a[..., :, None] == b[..., None, :], (-1, -2))
        return int(jnp.sum(w.top_k - same))

    # every program layer first, as bench/run.py orders program and
    # reference: nothing of the program is traced after the reference's
    # precision context has been entered
    x = prog_first(tokens)
    prog_idx = []
    for l in range(w.moe_layers):
        x, idx = prog_layer(x, jax.tree_util.tree_map(lambda t: t[l],
                                                      params["blocks"]))
        prog_idx.append(idx)
    jax.block_until_ready(prog_idx)
    del x
    with jax.default_matmul_precision("highest"):
        xr = [ref_first(t) for t in tokens]
    by_layer = []
    for l, idx in enumerate(prog_idx):
        sl = lambda t: t[l]
        with jax.default_matmul_precision("highest"):
            out = [ref_layer(xi, jax.tree_util.tree_map(sl, rest),
                             jax.tree_util.tree_map(sl, lo_rest))
                   for xi in xr]
        xr = [o[0] for o in out]
        n = sum(differ(idx[i], o[1]) for i, o in enumerate(out))
        by_layer.append(n / idx.size)
    return {"seed": seed, "share": sum(by_layer) / len(by_layer),
            "by_layer": by_layer}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    from bench import spec

    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    conf = spec.load_config(bench, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    import jax

    for seed in map(int, args.seeds.split(",")):
        print(json.dumps(flips(conf, traffic, seed)), flush=True)
        # as bench/tools/calibrate.py between runs: the next seed's program
        # is traced anew, with nothing kept from this seed's reference
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
