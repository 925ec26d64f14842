"""Resolve a cell of ``BENCHMARK.json`` to its files.

A cell names a configuration and a traffic mix. The configuration's file
(``bench/configs/<config>.json``) holds the published widths and names,
under ``architecture``, the module of its architecture
(``bench/archs/<name>.py``: weights, op counts, reference); the traffic
file (``bench/traffic/<traffic>.json``) the mix and the driver that runs
it; ``bench/limits/<cell>.json`` the limits of the correctness check; and
``bench/metrics/<metric>.py`` the reader of each per-layer metric. The
harness finds all of them by name or path, so a new cell, metric or
configuration, of any architecture, is new files and entries, never an
edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


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


def load_arch(conf: dict, root: str = ROOT):
    """The architecture module that ``conf`` names under ``architecture``,
    loaded by path, once per path. It holds:

    * ``HF_TO_ARCH`` and ``ASSUMED_TO_ARCH``: the keys of the file's
      ``published`` and ``assumed`` sections that the registry holds, each
      mapped to its ``ArchConfig`` field (``group.field`` inside a nested
      group, such as ``moe.top_k``);
    * ``refuse(cfg)``: raises :class:`CellError` for a registry entry with
      settings the module does not model;
    * ``Widths.from_config(conf)``, the sizes, with the LoRA ``rank``;
    * ``make_base(w, key, dtype)``, ``make_lora(w, key, dtype, b_std=0.0)``,
      ``to_program(base, lora, w)``, ``lora_tree(lora)``, ``lora_of(params)``;
    * ``train_flops_per_token(w, seq)``, ``lora_calls(w, batch, seq)``
      (target, M, K, N of each LoRA linear) and ``flash_ops(w, batch,
      seq)`` (FLOPs and bytes of one flash forward call and one
      backward), for the readers;
    * ``Reference(conf)`` (``sgd``, ``logits``) and ``quantize_int8``.
    """
    path = os.path.abspath(os.path.join(root, conf["architecture"]))
    name = "bench_arch:" + path
    if name in sys.modules:
        return sys.modules[name]
    if not os.path.isfile(path):
        raise CellError(f"{conf['name']}: no architecture module "
                        f"{conf['architecture']}")
    modspec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(modspec)
    sys.modules[name] = mod        # dataclasses look their module up here
    modspec.loader.exec_module(mod)
    return mod


def _field(obj, field: str):
    """A registry field by dotted path, at its resolved value where the
    config derives one (``resolved_head_dim`` for ``head_dim``)."""
    head, _, rest = field.partition(".")
    if rest:
        return _field(getattr(obj, head), rest)
    return getattr(obj, "resolved_" + head, getattr(obj, head))


def _replace(obj, changes: dict):
    """``obj`` with the dotted fields of ``changes`` replaced."""
    top, nested = {}, {}
    for field, value in changes.items():
        head, _, rest = field.partition(".")
        if rest:
            nested.setdefault(head, {})[rest] = value
        else:
            top[head] = value
    for head, sub in nested.items():
        top[head] = _replace(getattr(obj, head), sub)
    return dataclasses.replace(obj, **top)


def arch_config(conf: dict, root: str = ROOT):
    """The program's ArchConfig for ``conf``: the registry's entry, refused
    where the architecture module does not model it, and checked key by
    key against the published widths. A key listed in ``reduced`` is
    taken from the file; any other key that differs is an error."""
    from repro.configs import get_config

    arch = load_arch(conf, root)
    cfg = get_config(conf["arch"])
    arch.refuse(cfg)
    keys = [(conf[section][key], key, field)
            for section, keymap in (("published", arch.HF_TO_ARCH),
                                    ("assumed", arch.ASSUMED_TO_ARCH))
            for key, field in keymap.items()]
    lora = conf["assumed"]["lora"]
    reduced = set(conf.get("reduced", ()))
    changes = {}
    for want, key, field in keys:
        have = _field(cfg, field)
        if have != want:
            if key not in reduced:
                raise CellError(f"{conf['name']}: {key} is {want!r} "
                                f"in the file but {have!r} in the program's "
                                f"registry ({conf['arch']})")
            changes[field] = want
    if (cfg.lora.rank, cfg.lora.alpha, tuple(cfg.lora.targets)) != (
            lora["rank"], lora["alpha"], tuple(lora["targets"])):
        raise CellError(f"{conf['name']}: LoRA setting differs from the "
                        f"registry's {cfg.lora}")
    return _replace(cfg, changes) if changes else cfg


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
