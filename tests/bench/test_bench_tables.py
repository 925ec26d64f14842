"""The yardstick's tables: required FLOPs and bytes from shapes, and the
published peaks keyed by device kind."""
import json
import os

import pytest

from bench import flops, peaks, spec

ALL = ("q", "k", "v", "o", "gate", "up", "down")


def _config(name):
    with open(os.path.join(spec.BENCH_DIR, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def dense():
    """The dense decoder's architecture module, as the configurations
    name it."""
    return spec.load_arch(_config("qwen2.5-0.5b"))


def test_train_flops_hand_count(dense):
    w = dense.Widths(layers=2, d=8, ff=16, heads=2, kv_heads=1, head_dim=4,
                     vocab=10, tied=True, rank=2, targets=ALL)
    frozen = (64 + 32 + 32 + 64 + 128 * 3) * 2      # per-layer K·N, 2 layers
    head = 10 * 8
    lora = 6 * 2 * (16 + 12 + 12 + 16 + 24 * 3) * 2  # 6·r·(K+N)
    attn = 3 * 2 * 2 * (4 // 2) * 4 * 2 * 2          # fwd + 2x bwd, causal
    assert dense.train_flops_per_token(w, 4) == \
        4 * (frozen + head) + lora + attn


@pytest.mark.parametrize("name,seq,gflop", [
    ("qwen2.5-0.5b", 256, 2.04), ("qwen2.5-3b", 2048, 13.34)])
def test_train_flops_full_width(dense, name, seq, gflop):
    w = dense.Widths.from_config(_config(name))
    assert dense.train_flops_per_token(w, seq) / 1e9 == \
        pytest.approx(gflop, abs=0.005)


@pytest.mark.parametrize("kind,want", [
    ("fwd", (2 * 3 * (4 * 5 + 2 * 9), 2 * (12 + 20 + 18 + 15))),
    ("dx", (2 * 3 * (4 * 5 + 2 * 9), 2 * (15 + 20 + 18 + 12))),
    ("dab", (2 * 3 * 2 * 2 * 9, 2 * (12 + 15 + 2 * 18)))])
def test_lora_op_hand_count(kind, want):
    assert flops.lora_op(kind, 3, 4, 5, 2) == want


def test_flash_op_hand_count():
    f, b = flops.flash_op("fwd", 1, 2, 1, 4, 8)
    assert f == 2 * 2 * 1 * 2 * 4 * 2 * 8
    assert b == 2 * (2 * 64 + 2 * 32) + 2 * 4 * 4
    assert flops.flash_op("bwd", 1, 2, 1, 4, 8)[0] == 2 * f


def test_peaks_refuse_unknown_kind():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.roofline_seconds(1.0, 1.0, "cpu")


def test_roofline_takes_the_binding_bound():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert peaks.roofline_seconds(197e12, 1.0, "TPU v5 lite") == 1.0
    assert peaks.roofline_seconds(1.0, 819e9, "TPU v5 lite") == 1.0


def test_reference_imports_nothing_of_the_program():
    """The plain reference and the weights it reads, the architecture
    module's and the shared ones, stand apart from the program under
    test."""
    import subprocess
    import sys

    code = ("import json, sys; import bench.reference, bench.weights; "
            "from bench import spec; spec.load_arch(json.load(open("
            "'bench/configs/qwen2.5-0.5b.json'))); print(sorted(m for m in sys.modules if m.split('.')[0] == "
            "'repro'))")
    out = subprocess.run([sys.executable, "-c", code], cwd=spec.ROOT,
                         capture_output=True, text=True, timeout=120,
                         env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
                              "PYTHONPATH": spec.ROOT})
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"
