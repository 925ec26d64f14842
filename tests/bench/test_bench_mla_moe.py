"""The Moonlight-16B-A3B configuration and its architecture module
(``bench/archs/mla_moe.py``): the file holds the published config key for
key, the program matches the plain reference at a small size, the
reference stands apart from the program, and the expert reader parses the
expert kernels' calls."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec

CONF_FILE = os.path.join(spec.BENCH_DIR, "configs", "moonlight-16b-a3b.json")
CELL = "moonlight-16b-a3b.ft.s8192"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]

# the shape keys of Moonlight-16B-A3B's published config.json (the
# configuration's ``source``), as published
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
    "kv_lora_rank": 512, "max_position_embeddings": 8192,
    "model_type": "deepseek_v3", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts_per_tok": 6, "num_hidden_layers": 27,
    "num_key_value_heads": 16, "num_nextn_predict_layers": 0,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_theta": 50000, "routed_scaling_factor":
    2.446, "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 163840,
}


@pytest.fixture(scope="module")
def conf():
    with open(CONF_FILE) as f:
        return json.load(f)


def test_published_is_the_source_config(conf):
    """Every published key under the same key at the file's top level; only
    the three reduced keys differ, each to the chip's share. ``published``
    repeats them, since ``spec.arch_config`` reads the widths from there,
    and holds no other key."""
    pub = conf["published"]
    assert {k: conf[k] for k in PUBLISHED} == pub
    assert all(type(conf[k]) is type(pub[k]) for k in pub)
    assert set(pub) == set(PUBLISHED)
    differ = sorted(k for k in PUBLISHED if pub[k] != PUBLISHED[k]
                    or type(pub[k]) is not type(PUBLISHED[k]))
    assert differ == sorted(REDUCED)
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (6, 8, 20480)
    assert conf["reduced"] == REDUCED
    entry = [c for c in spec.load_benchmark()["configs"]
             if c["name"] == conf["name"]][0]
    assert entry["reduced"] == REDUCED and entry["source"] == conf["source"]
    dep = conf["deployment"]
    assert dep["routed_experts_published"] == PUBLISHED["n_routed_experts"]
    assert dep["layers_published"] == PUBLISHED["num_hidden_layers"]
    assert dep["vocab_published"] == PUBLISHED["vocab_size"]
    assert dep["experts_held"] == [0, 8] and dep["chips_sharing_each_layer"] \
        == PUBLISHED["vocab_size"] // pub["vocab_size"]


def test_router_width_and_share_are_the_registry_s(conf):
    cfg = spec.arch_config(conf)
    assert (cfg.moe.n_experts, cfg.moe.held_lo, cfg.moe.resolved_n_held) \
        == (conf["assumed"]["router_experts"], 0, 8)
    bad = json.loads(json.dumps(conf))
    bad["assumed"]["router_experts"] = 8
    with pytest.raises(spec.CellError, match="router_experts"):
        spec.arch_config(bad)


def _tiny(conf, held=8, held_from=0):
    """The configuration and the program's config at CPU widths: q·k 16 +
    8 against v 12, 8 experts top-3, 1 shared expert, dense layer 0."""
    from repro.configs import get_config

    pub = dict(conf["published"], hidden_size=64, num_attention_heads=4,
               num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=12, intermediate_size=96,
               moe_intermediate_size=32, n_routed_experts=held,
               num_experts_per_tok=3, n_shared_experts=1,
               num_hidden_layers=3, vocab_size=256)
    lora = dict(conf["assumed"]["lora"], rank=4, alpha=8.0)
    tiny = dict(conf, published=pub,
                assumed=dict(conf["assumed"], router_experts=8,
                             experts_held_from=held_from, dtype="float32",
                             lora=lora))
    c = get_config(conf["arch"])
    cfg = dataclasses.replace(
        c, n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=32,
        vocab=256, dtype="float32",
        moe=dataclasses.replace(c.moe, n_experts=8, top_k=3, d_expert=32,
                                n_shared=1, d_dense=96, n_held=held,
                                held_lo=held_from),
        mla=dataclasses.replace(c.mla, kv_lora_rank=32, qk_nope_head_dim=16,
                                qk_rope_head_dim=8, v_head_dim=12),
        lora=dataclasses.replace(c.lora, rank=4, alpha=8.0))
    return tiny, cfg


def _program_vs_reference(conf, backend, held=8, held_from=0, bias=None):
    from repro.api.policy import ExecutionPolicy
    from repro.core import mesp

    tiny, cfg = _tiny(conf, held, held_from)
    arch = spec.load_arch(conf)
    w = arch.Widths.from_config(tiny)
    key = jax.random.PRNGKey(7)
    base = arch.make_base(w, key, "float32")
    if bias is not None:
        base["score_bias"] = base["score_bias"] + bias
    lora = arch.make_lora(w, jax.random.fold_in(key, 1), "float32", 0.1)
    toks = np.random.default_rng(0).integers(0, 256, (2, 81)).astype(
        np.int32)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    ref_loss, ref_g = arch.Reference(tiny).loss_and_grads(base, lora, batch)
    pol = ExecutionPolicy(backend=backend)
    loss, g, aux = jax.jit(lambda p, b: mesp.value_grad_and_aux(
        p, cfg, b, policy=pol))(arch.to_program(base, lora, w), batch)
    g = arch.lora_of(g)
    assert set(g) == set(ref_g)
    assert abs(float(loss) - ref_loss) <= 2e-6 * abs(ref_loss)
    for t in ref_g:
        for k in ("a", "b"):
            gap = float(jnp.linalg.norm(g[t][k] - ref_g[t][k])
                        / jnp.linalg.norm(ref_g[t][k]))
            assert gap <= 5e-5, (t, k, gap)
    return {k: int(v) for k, v in aux.items()}


@pytest.mark.parametrize("backend", ["pallas", "structured"])
def test_program_matches_the_reference(conf, backend):
    """Loss and every LoRA gradient leaf at a small size, the program's
    kernels in interpret mode; float32 both, so the gaps are round-off
    (5e-5 of a leaf's norm: the reference's HIGHEST matmuls against the
    program's summation orders)."""
    aux = _program_vs_reference(conf, backend)
    assert aux["dropped"] == 0 and aux["routed_rows"] == 2 * 2 * 80 * 3


def test_one_expert_taking_every_token_matches_the_reference(conf):
    """A share of 2 experts, every token routed to the first (its rows are
    all the batch's tokens): the program still matches the reference,
    nothing dropped."""
    bias = jnp.zeros((8,), jnp.float32).at[0].set(100.0)
    aux = _program_vs_reference(conf, "pallas", held=2, bias=bias)
    assert aux["dropped"] == 0 and aux["routed_rows"] >= 2 * 2 * 80


def test_reference_imports_nothing_of_the_program():
    code = ("import json, sys; import bench.reference, bench.weights; "
            "from bench import spec; spec.load_arch(json.load(open("
            "'bench/configs/moonlight-16b-a3b.json'))); print(sorted(m for m "
            "in sys.modules if m.split('.')[0] == 'repro'))")
    out = subprocess.run([sys.executable, "-c", code], cwd=spec.ROOT,
                         capture_output=True, text=True, timeout=120,
                         env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
                              "PYTHONPATH": spec.ROOT})
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"


def test_cell_op_counts(conf):
    """The cell's counts by hand: 2.020 GFLOP a token, 0.75 expert slots a
    token over the 8 held experts, flash at q·k 192 against v 128."""
    arch = spec.load_arch(conf)
    w = arch.Widths.from_config(conf)
    assert arch.train_flops_per_token(w, 8192) == 2020307456
    assert arch.expert_calls(w, 4, 8192) == [
        ("gate", 24576, 2048, 1408, 8), ("up", 24576, 2048, 1408, 8),
        ("down", 24576, 1408, 2048, 8)]
    (ff, fb), (bf, bb) = arch.flash_ops(w, 4, 8192)
    assert ff == 2 * 4 * 16 * 8192 * 4096 * (192 + 128) and bf == 2 * ff


class _Trace:
    def __init__(self, events):
        self.events = events

    def op_events(self, t0, t1, names):
        return [e for e in self.events if e[0] in names]


def test_expert_reader_on_kernel_calls(conf):
    arch = spec.load_arch(conf)
    w = arch.Widths.from_config(conf)
    ev = [("expert_fwd", "%expert_fwd.3 = bf16[200704,1536] custom-call("
           "s32[392] %a, s32[1] %b, bf16[200704,2048] %c, bf16[8,2048,1536] "
           "%d, bf16[8,2048,8] %e, bf16[8,8,1536] %f)", 2e6),
          ("expert_dx", "%expert_dx.1 = bf16[200704,2048] custom-call("
           "s32[392] %a, s32[1] %b, bf16[200704,1536] %g, bf16[8,2048,1536] "
           "%w, bf16[200704,8] %h, bf16[8,2048,8] %i)", 2e6),
          ("expert_dab", "%expert_dab.2 = (f32[8,2048,8], f32[8,8,1536]) "
           "custom-call(s32[392] %a, s32[1] %b, bf16[200704,2048] %x, "
           "bf16[200704,1536] %g, bf16[8,2048,8] %e, bf16[8,8,1536] %f)",
           1e6)]
    from bench.peaks import peaks
    ctx = {"window": (0, 1, 2), "widths": w, "arch": arch,
           "traffic": {"batch": 4, "seq": 8192}, "trace": _Trace(ev),
           "kind": "TPU v5 lite", "peaks": peaks("TPU v5 lite")}
    read = spec.load_reader("train.expert_roofline")
    got = read(ctx)
    need = sum(max(f / 197e12, b / 819e9) for f, b in (
        arch.expert_op("fwd", 24576, 2048, 1408, 8, 8),
        arch.expert_op("dx", 24576, 2048, 1408, 8, 8),
        arch.expert_op("dab", 24576, 2048, 1408, 8, 8)))
    assert got == pytest.approx(100 * need / 5e-3)
    assert read({**ctx, "trace": _Trace([("expert_fwd", "expert_fwd.3",
                                          1e6)])}) is None
    assert read({**ctx, "trace": _Trace([])}) is None


def test_route_flips_reads_no_flip_in_float32(conf):
    """``bench/tools/route_flips.py`` at CPU widths: in float32 the program
    and the reference choose the same experts at every MoE layer."""
    from bench.tools import route_flips

    tiny, cfg = _tiny(conf)
    got = route_flips.flips(tiny, {"engine": "mesp_pallas", "seq": 32,
                                   "batch": 2}, 3017, cfg=cfg)
    assert got["share"] == 0.0 and len(got["by_layer"]) == 2


def test_scope_time_reads_named_scopes_from_hlo_metadata():
    """``bench/tools/scope_time.py``: an operation's time goes to the named
    scope in its HLO metadata (a fusion without one takes its fused
    computation's), forward and backward alike; the rest to ``other``."""
    from bench.tools import scope_time

    hlo = "\n".join([
        "HloModule jit_step, entry_computation_layout={()->()}",
        "%fused_computation.1 (p: f32[8]) -> f32[8] {",
        '  %gather.3 = f32[8]{0} gather(%p), metadata={op_name="jit(step)'
        '/jvp()/while/body/moe/dispatch/gather"}',
        "}",
        "ENTRY %main.9 (a: f32[8]) -> f32[8] {",
        "  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, "
        "calls=%fused_computation.1",
        '  %flash_fwd.2 = f32[8]{0} custom-call(%a), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(step)/mla/pallas_call"}',
        '  %add.4 = f32[8]{0} add(%a, %a), metadata={op_name="jit(step)/'
        'transpose(jvp(moe/combine))/add"}',
        "  ROOT %copy.5 = f32[8]{0} copy(%a)",
        "}"])
    assert scope_time.instruction_scopes(hlo) == {
        "gather.3": "moe/dispatch", "fusion.1": "moe/dispatch",
        "flash_fwd.2": "mla", "add.4": "moe/combine"}
    assert scope_time.scope_of("jit(step)/mlatest/x") is None
    mod = [["jit_step(1)", 0, 100], ["jit_step(1)", 200, 100],
           ["jit_step(1)", 400, 100]]
    ops = [["%fusion.1 = f32[8]{0} fusion(%a)", 0, 30],
           ["flash_fwd.2", 30, 50], ["copy.5", 80, 20],
           ["fusion.1", 200, 30], ["flash_fwd.2", 230, 50],
           ["add.4", 280, 20]]
    got = scope_time.by_scope(
        {"devices": [{"plane": "/device:TPU:0", "modules": mod,
                      "ops": ops}], "host": []}, hlo, "jit_step")
    assert got["steps"] == 2 and got["op_s"] == 200e-9
    assert {k: v["s"] for k, v in got["scopes"].items()} == {
        "mla": 100e-9, "moe/dispatch": 60e-9, "other": 20e-9,
        "moe/combine": 20e-9}
