"""``chip_smoke.py`` never reports success off the chip, and the compile
cache lands where the entry points say."""
import os
import shutil
import subprocess
import sys

import pytest

from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("case", ["cpu", "alone", "interpret"])
def test_chip_smoke_fails_without_chip(case, tmp_path):
    """On the CPU, from a directory holding the script and nothing else of
    the repo, or asked for interpret mode: non-zero exit, no ok line."""
    if case == "alone":
        shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
        proc = _run(["chip_smoke.py"], cwd=tmp_path)
    elif case == "interpret":
        proc = _run([SMOKE, "--pallas-interpret", "on"], cwd=tmp_path)
    else:
        proc = _run([SMOKE], cwd=tmp_path)
    assert proc.returncode != 0, proc.stdout
    assert '"ok": true' not in proc.stdout
    assert '"ok"' not in proc.stdout


def test_chip_smoke_counts_named_kernels():
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    text = "\n".join([
        '  %lora_fwd.3 = bf16[256,896]{1,0} custom-call(%a, %b), '
        'custom_call_target="tpu_custom_call", backend_config={}',
        '  %flash_dq = bf16[14,256,64]{2,1,0} custom-call(%q), '
        'custom_call_target="tpu_custom_call"',
        '  %lora_fwd.7 = bf16[256,896]{1,0} custom-call(%c), '
        'custom_call_target="tpu_custom_call"',
        '  %dot.1 = f32[8,8]{1,0} dot(%x, %y)',
    ])
    assert chip_smoke.kernel_counts(text) == {"lora_fwd": 2, "flash_dq": 1}


def test_compile_cache_env_set_sets_nothing():
    env = {compile_cache.ENV: "/some/where"}
    assert compile_cache.cache_settings(env, root="/repo") == {}


def test_compile_cache_env_unset_uses_checkout_path():
    got = compile_cache.cache_settings({}, root="/repo")
    assert got == {
        "jax_compilation_cache_dir": os.path.join("/repo", ".jax_cache"),
        "jax_persistent_cache_min_compile_time_secs": 0.0,
    }
    # the default root is this checkout, a fixed path
    default = compile_cache.cache_settings({})
    assert default["jax_compilation_cache_dir"] == os.path.join(
        ROOT, ".jax_cache")
