"""Architecture modules: a configuration of another family reaches the
harness as new files and entries alone, and the dense module's weights,
reference and op counts stay as they were before it moved (pins)."""
import hashlib
import json
import os
import shutil

import jax
import numpy as np
import pytest

from bench import peaks, spec, weights

REPO = spec.ROOT
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TINY = os.path.join(DATA, "tiny", "bench", "configs", "tiny.json")
SEED = 5001

# olmoe-1b-7b of the program's registry at a size the CPU can hold; every
# key that differs from the registry is listed in ``reduced``
OLMOE = {
    "name": "olmoe-tiny",
    "arch": "olmoe-1b-7b",
    "source": "https://huggingface.co/allenai/OLMoE-1B-7B-0924/blob/main/"
              "config.json",
    "architecture": "bench/archs/moe.py",
    "published": {
        "hidden_size": 64, "intermediate_size": 32,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "num_hidden_layers": 2, "num_experts": 4, "num_experts_per_tok": 2,
        "rope_theta": 10000.0, "tie_word_embeddings": False,
        "torch_dtype": "bfloat16", "vocab_size": 256,
    },
    "reduced": ["hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "num_hidden_layers", "num_experts",
                "num_experts_per_tok", "vocab_size", "head_dim"],
    "assumed": {
        "head_dim": 16,
        "lora": {"rank": 8, "alpha": 16.0,
                 "targets": ["q", "k", "v", "o", "gate", "up", "down"]},
    },
}


def _tree_hash(*trees) -> str:
    h = hashlib.sha256()
    for tree in trees:
        leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
        for path, leaf in sorted(leaves,
                                 key=lambda t: jax.tree_util.keystr(t[0])):
            a = np.asarray(leaf)
            h.update(jax.tree_util.keystr(path).encode())
            h.update(str(a.dtype).encode())
            h.update(str(a.shape).encode())
            h.update(a.tobytes())
    return h.hexdigest()


def _shapes(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


@pytest.fixture
def moe_root(tmp_path):
    """A checkout with a cell of the MoE configuration added: a
    configuration file, an architecture module, a traffic file, a limits
    file and entries in ``BENCHMARK.json``; no file that was there is
    edited."""
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench")
    shutil.copy(os.path.join(DATA, "archs", "moe.py"),
                tmp_path / "bench" / "archs" / "moe.py")
    (tmp_path / "bench" / "configs" / "olmoe-tiny.json").write_text(
        json.dumps(OLMOE))
    traffic = spec.load_traffic("ft.paper")
    traffic["why"] = "a MoE mix"
    (tmp_path / "bench" / "traffic" / "moe.ft.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench" / "limits" / "olmoe-tiny.moe.ft.json").write_text(
        json.dumps({"limits": {"loss_gap": 1.0}}))
    bench = spec.load_benchmark()
    bench["configs"].append({
        "name": "olmoe-tiny", "source": OLMOE["source"],
        "file": "bench/configs/olmoe-tiny.json",
        "reduced": OLMOE["reduced"], "why": "a MoE decoder"})
    bench["workloads"].append({"name": "olmoe-tiny.moe.ft",
                               "config": "olmoe-tiny", "traffic": "moe.ft",
                               "chips": 1, "why": "a MoE cell"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("train_tokens_per_s", "train.mfu"):
            m["workloads"].append("olmoe-tiny.moe.ft")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def test_config_of_another_family_needs_no_edit(moe_root):
    """The MoE configuration resolves through its own module: registry
    entry, weights in the program's tree, metrics and the mfu reader."""
    from repro.models import model as model_lib

    b = spec.load_benchmark(moe_root)
    cell = spec.find_cell(b, "olmoe-tiny.moe.ft")
    conf = spec.load_config(b, cell["config"], moe_root)
    arch = spec.load_arch(conf, moe_root)
    assert arch.__file__ == os.path.join(moe_root, "bench", "archs",
                                         "moe.py")
    cfg = spec.arch_config(conf, moe_root)
    assert (cfg.family, cfg.n_layers, cfg.d_model, cfg.vocab) == \
        ("moe", 2, 64, 256)
    assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.d_expert) == (4, 2, 32)
    assert cfg.resolved_head_dim == 16

    w = arch.Widths.from_config(conf)
    key = weights.root_key(SEED)
    lora = arch.make_lora(w, jax.random.fold_in(key, 1), cfg.dtype)
    params = arch.to_program(arch.make_base(w, key, cfg.dtype), lora, w)
    want = jax.eval_shape(lambda: model_lib.init_params(
        jax.random.PRNGKey(0), cfg))
    have = _shapes(params)
    assert jax.tree_util.tree_structure(have) == \
        jax.tree_util.tree_structure(want)
    assert have == want
    assert _tree_hash(arch.lora_of(params)) == _tree_hash(lora)
    assert _shapes(arch.lora_tree(lora)) == _shapes(
        {"blocks": {g: {t: {"a": p["a"], "b": p["b"]}
                        for t, p in want["blocks"][g].items()
                        if isinstance(p, dict) and "a" in p}
                    for g in ("attn", "moe")}})

    names = {m["name"] for m in spec.metrics_of_cell(b, cell, trace=False)}
    assert names == {"train_tokens_per_s", "peak_hbm_gb", "setup_s"}
    layer = [m["name"] for m in spec.metrics_of_cell(b, cell, trace=True)]
    assert layer == ["train.mfu"]
    traffic = spec.load_traffic(cell["traffic"], moe_root)
    ctx = {"window": (0, 2_000_000_000, 4), "traffic": traffic,
           "widths": w, "arch": arch, "peaks": peaks.peaks("TPU v5 lite")}
    tokens = 4 * traffic["batch"] * traffic["seq"]
    flops = arch.train_flops_per_token(w, traffic["seq"])
    assert flops > 0
    assert spec.load_reader("train.mfu", moe_root)(ctx) == pytest.approx(
        100.0 * tokens * flops / (2.0 * 197e12), rel=1e-12)


def test_nested_reduced_key_must_be_listed(moe_root):
    conf = dict(OLMOE, reduced=[k for k in OLMOE["reduced"]
                                if k != "num_experts_per_tok"])
    with pytest.raises(spec.CellError, match="num_experts_per_tok"):
        spec.arch_config(conf, moe_root)


def test_dense_module_refuses_another_family():
    conf = dict(OLMOE, architecture="bench/archs/dense.py")
    with pytest.raises(spec.CellError, match="not a dense decoder"):
        spec.arch_config(conf)


# ------------------------------------------------- dense pins (parent tree)

def _tiny():
    with open(TINY) as f:
        conf = json.load(f)
    arch = spec.load_arch(conf)
    w = arch.Widths.from_config(conf)
    key = weights.root_key(SEED)
    base = arch.make_base(w, key, "bfloat16")
    lora = arch.make_lora(w, jax.random.fold_in(key, 1), "bfloat16")
    return conf, arch, base, lora


def test_dense_weights_pinned():
    """Seeded base and LoRA factors at the tiny widths, bit for bit."""
    _, _, base, lora = _tiny()
    assert _tree_hash(base, lora) == \
        "5873b22dc164b4725a72600ea78a6f935707196ffa5a3d119a2c98fca8421a07"


def test_dense_reference_loss_pinned():
    from bench.data import Windows

    conf, arch, base, lora = _tiny()
    batch = next(Windows(512, 64, 2, SEED))
    loss, _ = arch.Reference(conf).loss_and_grads(base, lora, batch)
    assert loss == 6.294581413269043


@pytest.mark.parametrize("name,seq,flops", [
    ("qwen2.5-0.5b", 256, 2_035_269_632),
    ("qwen2.5-3b", 2048, 13_338_558_464)])
def test_dense_train_flops_pinned(name, seq, flops):
    with open(os.path.join(spec.BENCH_DIR, "configs", f"{name}.json")) as f:
        conf = json.load(f)
    arch = spec.load_arch(conf)
    assert arch.train_flops_per_token(arch.Widths.from_config(conf),
                                      seq) == flops
