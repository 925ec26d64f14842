"""Sharding spec construction + HLO roofline analyzer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import ASSIGNED, get_config
from repro.launch import sharding as sh
from repro.roofline.hlo_parse import HloModule, analyze_text
from repro.roofline.analysis import collective_bytes, model_flops
from repro.configs.base import SHAPES


@pytest.mark.parametrize("arch", ASSIGNED)
def test_param_specs_rank_matches(arch):
    cfg = get_config(arch).reduced()
    params = jax.eval_shape(
        lambda: __import__("repro.models.model", fromlist=["m"]).init_params(
            jax.random.PRNGKey(0), cfg))
    specs = sh.param_specs(cfg, params)
    flat_p = jax.tree_util.tree_leaves_with_path(params)
    flat_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    for (path, leaf), spec in zip(flat_p, flat_s):
        assert len(spec) <= leaf.ndim, (path, leaf.shape, spec)


def test_divisibility_guard(fake_mesh):
    cfg = get_config("whisper-tiny")  # vocab 51865: not divisible by 16
    mesh = fake_mesh(16, 16)
    params = jax.eval_shape(
        lambda: __import__("repro.models.model", fromlist=["m"]).init_params(
            jax.random.PRNGKey(0), cfg))
    specs = sh.param_specs(cfg, params, mesh)
    head_spec = specs["embed"]["head"]
    assert head_spec == P(None, None)  # guarded off
    # q projection (384 -> 384) IS divisible: stays sharded
    q_spec = specs["blocks"]["attn"]["q"]["w"]
    assert q_spec[-1] == "model"


def test_moe_expert_parallel_specs():
    cfg = get_config("olmoe-1b-7b")
    params = jax.eval_shape(
        lambda: __import__("repro.models.model", fromlist=["m"]).init_params(
            jax.random.PRNGKey(0), cfg))
    specs = sh.param_specs(cfg, params)
    # stacked [L, E, d, f] expert weights: E dim sharded on model
    w_spec = specs["blocks"]["moe"]["gate"]["w"]
    assert tuple(w_spec) == (None, "model", None, None)


def test_batch_spec_fallbacks(fake_mesh):
    mesh = fake_mesh(16, 16)
    spec = tuple(sh.batch_spec(mesh, 256))
    assert spec in ((("data",),), ("data",))  # P may normalize 1-tuples
    assert tuple(sh.batch_spec(mesh, 1)) == ()


# ------------------------------------------------------------------ roofline
def test_hlo_analyzer_counts_scan_trips():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        return jax.lax.scan(body, x, None, length=9)[0]

    s = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    compiled = jax.jit(f).lower(s, s).compile()
    t = analyze_text(compiled.as_text())
    assert t.flops == pytest.approx(2 * 128**3 * 9, rel=1e-6)


def test_hlo_analyzer_nested_while():
    def f(x, w):
        def inner(c, _):
            return c @ w, None

        def outer(c, _):
            c, _ = jax.lax.scan(inner, c, None, length=3)
            return c, None

        return jax.lax.scan(outer, x, None, length=4)[0]

    s = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    compiled = jax.jit(f).lower(s, s).compile()
    t = analyze_text(compiled.as_text())
    assert t.flops == pytest.approx(2 * 64**3 * 12, rel=1e-6)


def test_collective_regex():
    text = """
ENTRY %main () -> () {
  %ag = bf16[16,1024]{1,0} all-gather(bf16[16,64]{1,0} %x), replica_groups={}
  %ar.1 = f32[256,256]{1,0} all-reduce(f32[256,256]{1,0} %y), to_apply=%sum
}
"""
    out = collective_bytes(text)
    assert out["all-gather"] == 16 * 1024 * 2
    assert out["all-reduce"] == 256 * 256 * 4


def test_collective_parse_combined_and_async():
    """XLA combines the per-layer gradient all-reduces into one op with a
    tuple result: every element is payload. Async starts of all-gather
    pair the operand with the output: only the output counts. A
    reduce-scatter counts its input, as ``hlo_parse`` counts it."""
    text = """
ENTRY %main () -> () {
  %a = f32[64,4]{1,0} parameter(0)
  %b = f32[4,32]{1,0} parameter(1)
  %c = f32[128,4]{1,0} parameter(2)
  %d = s32[] parameter(3)
  %e = s32[] parameter(4)
  %x = bf16[16,64]{1,0} parameter(5)
  %z = f32[32]{0} parameter(6)
  %ar = (f32[64,4]{1,0}, f32[4,32]{1,0}, /*index=2*/f32[128,4]{1,0}) all-reduce(%a, %b, %c), to_apply=%sum
  %s = (s32[], s32[]) all-reduce(%d, %e), to_apply=%sum
  %ags = (bf16[16,64]{1,0}, bf16[16,1024]{1,0}) all-gather-start(%x), dimensions={1}
  %agd = bf16[16,1024]{1,0} all-gather-done(%ags)
  %ars = f32[32]{0} all-reduce-start(%z), to_apply=%sum
  %rs = f32[8]{0} reduce-scatter(%z), dimensions={0}, to_apply=%sum
}
"""
    out = collective_bytes(text)
    assert out["all-reduce"] == (64 * 4 + 4 * 32 + 128 * 4) * 4 + 8 + 32 * 4
    assert out["all-gather"] == 16 * 1024 * 2
    assert out["reduce-scatter"] == 32 * 4
    assert analyze_text(text).coll == {k: float(v) for k, v in out.items()
                                       if v}


def test_model_flops_accounting():
    cfg = get_config("olmoe-1b-7b")
    dense_equiv = get_config("granite-8b")
    # MoE active < total
    assert cfg.n_active_params() < cfg.n_params()
    tr = model_flops(cfg, SHAPES["train_4k"])
    de = model_flops(cfg, SHAPES["decode_32k"])
    assert tr > de  # decode touches 1 token per sequence
    assert model_flops(dense_equiv, SHAPES["train_4k"]) == pytest.approx(
        6 * dense_equiv.n_params() * 256 * 4096)
