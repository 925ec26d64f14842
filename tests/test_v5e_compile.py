"""Main-path Pallas kernels compile for a TPU v5e, with no chip attached.

Interpret mode (what every other kernel test runs) cannot see what the TPU
compiler refuses: block shapes off the (8, 128) tiling, relayouts Mosaic
cannot lower, more VMEM than a kernel may use. Each test here compiles one
kernel's forward and backward at Qwen2.5-0.5B widths (M = 256 rows,
K = 896, N = 4864, rank 8, 14 q heads over 2 kv heads of 64, bf16) for a
described ``v5e:2x2`` topology and checks that the compiled program runs
the named Pallas kernels (``tpu_custom_call``).

Moonlight-16B-A3B's kernels are compiled at its cell's shapes: flash at a
q·k width of 192 against v 128 over 8192 positions and 16 heads, the
routed-expert kernels between d 2048 and 1408 on a device-side tile
schedule, and RMSNorm's backward over the cell's 4 × 8192 rows.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every pytest worker imports this file.
"""
import contextlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.api.policy import ExecutionPolicy
from repro.kernels import ops
from repro.kernels.rope import rope_apply

M, K, N, R = 256, 896, 4864, 8
H, HKV, D = 14, 2, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _compile(f, *args):
    with _no_persistent_cache():
        return jax.jit(f).lower(*args).compile().as_text()


def _assert_kernels(text, *names):
    assert "tpu_custom_call" in text
    for name in names:
        assert f"%{name}" in text, (name, "not in the compiled program")


def _grad_sum(f, argnums):
    """Loss and grads of sum(f): the forward stays live in the program."""
    return jax.value_and_grad(lambda *a: f(*a).astype(jnp.float32).sum(),
                              argnums)


def _s(dev, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)


def test_lora_fused_vjp_compiles(one_chip):
    def f(x, w, a, b):
        return _grad_sum(lambda x, a, b: ops.lora_linear_kernel(
            x, w, a, b, 2.0, False), (0, 1, 2))(x, a, b)

    text = _compile(f, _s(one_chip, (M, K)), _s(one_chip, (K, N)),
                    _s(one_chip, (K, R)), _s(one_chip, (R, N)))
    _assert_kernels(text, "lora_fwd", "lora_dx", "lora_dab")


def test_lora_quant_int8_vjp_compiles(one_chip):
    def f(x, q, s, a, b):
        return _grad_sum(lambda x, a, b: ops.lora_linear_kernel_q(
            x, q, s, a, b, 2.0, False), (0, 1, 2))(x, a, b)

    text = _compile(f, _s(one_chip, (M, K)), _s(one_chip, (K, N), jnp.int8),
                    _s(one_chip, (1, N), jnp.float32),
                    _s(one_chip, (K, R)), _s(one_chip, (R, N)))
    _assert_kernels(text, "lora_q_fwd", "lora_q_dx", "lora_dab")


@pytest.mark.parametrize("method", ["int4", "nf4"])
def test_lora_pack4_vjp_compiles(one_chip, method):
    def f(x, q4, s, a, b):
        return _grad_sum(lambda x, a, b: ops.lora_linear_kernel_p4(
            x, q4, s, a, b, 2.0, False, method), (0, 1, 2))(x, a, b)

    text = _compile(f, _s(one_chip, (M, K)),
                    _s(one_chip, (K // 2, N), jnp.uint8),
                    _s(one_chip, (1, N), jnp.float32),
                    _s(one_chip, (K, R)), _s(one_chip, (R, N)))
    _assert_kernels(text, "lora_q4_fwd", "lora_q4_dx", "lora_dab")


@pytest.mark.parametrize("seq", [256, 512])
def test_flash_fwd_bwd_compiles(one_chip, seq):
    def f(q, k, v):
        return _grad_sum(lambda q, k, v: ops.flash_attention(
            q, k, v, True, 0, False, None), (0, 1, 2))(q, k, v)

    text = _compile(f, _s(one_chip, (1, H, seq, D)),
                    _s(one_chip, (1, HKV, seq, D)),
                    _s(one_chip, (1, HKV, seq, D)))
    _assert_kernels(text, "flash_fwd", "flash_dq", "flash_dkv")


@pytest.mark.parametrize("rows,d", [(M, K), (4 * M, K), (4 * 8192, 2048)])
def test_rmsnorm_vjp_compiles(one_chip, rows, d):
    """One row block (batch 1), several (batch 4 on one chip), and the
    Moonlight cell's 4 × 8192 rows of 2048."""
    def f(x, w):
        return _grad_sum(lambda x, w: ops.rmsnorm_kernel(x, w, 1e-6, False),
                         (0, 1))(x, w)

    text = _compile(f, _s(one_chip, (rows, d)), _s(one_chip, (d,)))
    _assert_kernels(text, "rmsnorm_fwd", "rmsnorm_bwd")


def test_flash_qk_192_v_128_compiles(one_chip):
    """Latent attention's flash call at the Moonlight cell's shapes: one
    sequence of 8192, 16 heads, q·k 192 against v 128."""
    def f(q, k, v):
        return _grad_sum(lambda q, k, v: ops.flash_attention(
            q, k, v, True, 0, False, None), (0, 1, 2))(q, k, v)

    text = _compile(f, _s(one_chip, (1, 16, 8192, 192)),
                    _s(one_chip, (1, 16, 8192, 192)),
                    _s(one_chip, (1, 16, 8192, 128)))
    _assert_kernels(text, "flash_fwd", "flash_dq", "flash_dkv")


@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)])
def test_expert_kernels_compile(one_chip, k, n):
    """The routed-expert linear (fwd, dx, dab) of a Moonlight MoE layer: 8
    held experts, rows for 4 × 8192 tokens at 6 slots in 512-row tiles,
    the tile schedule and live count as device arrays."""
    policy = ExecutionPolicy(backend="pallas", interpret=False)
    rows, bm = 4 * 8192 * 6 + 8 * 512, 512

    def f(x, w, a, b, gid, live):
        return _grad_sum(lambda x, a, b: ops.lora_expert_linear(
            x, w, a, b, gid, live, 2.0, bm=bm, policy=policy),
            (0, 1, 2))(x, a, b)

    text = _compile(f, _s(one_chip, (rows, k)), _s(one_chip, (8, k, n)),
                    _s(one_chip, (8, k, R)), _s(one_chip, (8, R, n)),
                    _s(one_chip, (rows // bm,), jnp.int32),
                    _s(one_chip, (), jnp.int32))
    _assert_kernels(text, "expert_fwd", "expert_dx", "expert_dab")


def test_rope_vjp_compiles(one_chip):
    def f(x, cos, sin):
        return _grad_sum(lambda x: rope_apply(x, cos, sin, False), 0)(x)

    text = _compile(f, _s(one_chip, (1, M, H, D)),
                    _s(one_chip, (M, D // 2), jnp.float32),
                    _s(one_chip, (M, D // 2), jnp.float32))
    _assert_kernels(text, "rope")


def test_lora_grouped_decode_compiles(one_chip):
    """The serving decode path: 4 slots in tiles of 2 rows (padded to the
    8-row sublane block inside), 2 resident adapters, runtime routing."""
    policy = ExecutionPolicy(backend="pallas", interpret=False)

    def f(x, w, a, b, gid):
        return ops.lora_grouped_decode(x, w, a, b, gid, bm=2, policy=policy)

    text = _compile(f, _s(one_chip, (4, K)), _s(one_chip, (K, N)),
                    _s(one_chip, (2, K, R)), _s(one_chip, (2, R, N)),
                    _s(one_chip, (2,), jnp.int32))
    _assert_kernels(text, "lora_grouped_fwd")
