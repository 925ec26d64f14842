"""Resolve a cell of ``BENCHMARK.json`` to its files.

A cell names a configuration and a traffic mix. The configuration's file
(``bench/configs/<config>.json``) holds the published widths; the traffic
file (``bench/traffic/<traffic>.json``) the mix and the driver that runs
it; ``bench/limits/<cell>.json`` the limits of the correctness check; and
``bench/metrics/<metric>.py`` the reader of each per-layer metric. The
harness finds all of them by name, so a new cell or metric is new files
and entries, never an edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

# config.json key -> ArchConfig field of the program's registry
HF_TO_ARCH = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "num_hidden_layers": "n_layers", "vocab_size": "vocab",
    "tie_word_embeddings": "tie_embeddings", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "torch_dtype": "dtype",
}
ASSUMED_TO_ARCH = {"head_dim": "head_dim", "attention_bias": "qkv_bias"}


class CellError(Exception):
    """The cell, or one of its files, is missing or does not agree."""


def _read_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise CellError(f"missing file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise CellError(f"no workload {workload!r} in BENCHMARK.json")


def load_config(bench: dict, name: str, root: str = ROOT) -> dict:
    for entry in bench["configs"]:
        if entry["name"] == name:
            conf = _read_json(os.path.join(root, entry["file"]))
            if sorted(conf.get("reduced", [])) != sorted(entry["reduced"]):
                raise CellError(f"config {name}: 'reduced' differs between "
                                f"BENCHMARK.json and {entry['file']}")
            return conf
    raise CellError(f"no configuration {name!r} in BENCHMARK.json")


def load_traffic(name: str, root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "bench", "traffic", f"{name}.json"))


def load_limits(workload: str, root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "bench", "limits",
                                   f"{workload}.json"))["limits"]


def arch_config(conf: dict):
    """The program's ArchConfig for ``conf``: the registry's entry, checked
    key by key against the published widths. A key listed in ``reduced``
    is taken from the file; any other key that differs is an error."""
    from repro.configs import get_config

    cfg = get_config(conf["arch"])
    want = {}
    for key, field in HF_TO_ARCH.items():
        want[field] = conf["published"][key]
    for key, field in ASSUMED_TO_ARCH.items():
        want[field] = conf["assumed"][key]
    lora = conf["assumed"]["lora"]
    reduced = set(conf.get("reduced", ()))
    changes = {}
    for key, field in {**HF_TO_ARCH, **ASSUMED_TO_ARCH}.items():
        have = getattr(cfg, field)
        if field == "head_dim":
            have = cfg.resolved_head_dim
        if have != want[field]:
            if key not in reduced:
                raise CellError(f"{conf['name']}: {key} is {want[field]!r} "
                                f"in the file but {have!r} in the program's "
                                f"registry ({conf['arch']})")
            changes[field] = want[field]
    if (cfg.lora.rank, cfg.lora.alpha, tuple(cfg.lora.targets)) != (
            lora["rank"], lora["alpha"], tuple(lora["targets"])):
        raise CellError(f"{conf['name']}: LoRA setting differs from the "
                        f"registry's {cfg.lora}")
    if cfg.family != "dense" or cfg.window_pattern:
        raise CellError(f"{conf['arch']} is not a dense decoder")
    return dataclasses.replace(cfg, **changes) if changes else cfg


def metrics_of_cell(bench: dict, cell: dict, trace: bool) -> list:
    """The metric entries a run of ``cell`` reports: its end-to-end metrics
    with ``--trace 0``, its per-layer metrics with ``--trace 1``."""
    e2e = [m for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def load_reader(metric: str, root: str = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "metrics", f"{metric}.py")
    if not os.path.isfile(path):
        raise CellError(f"no reader bench/metrics/{metric}.py")
    modspec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(modspec)
    modspec.loader.exec_module(mod)
    return mod.read
