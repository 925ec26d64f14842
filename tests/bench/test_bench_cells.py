"""Every cell of ``BENCHMARK.json`` resolves to its files, the files agree
with the program's registry, and a new cell or metric needs only new files
and entries."""
import json
import os
import re
import shutil

import pytest

from bench import spec

REPO = spec.ROOT
BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


CONFIG_FILES = sorted(os.listdir(os.path.join(spec.BENCH_DIR, "configs")))


@pytest.mark.parametrize("fname", CONFIG_FILES)
def test_config_widths_equal_the_registry(fname):
    """Every configuration file, in a cell or kept for a later one."""
    from repro.configs import get_config

    with open(os.path.join(spec.BENCH_DIR, "configs", fname)) as f:
        conf = json.load(f)
    assert spec.arch_config(conf) == get_config(conf["arch"])
    for entry in BENCH["configs"]:
        if entry["file"].endswith("/" + fname):
            assert spec.load_config(BENCH, entry["name"]) == conf
            assert conf["source"] == entry["source"]


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_resolves(cell):
    import importlib

    conf = spec.load_config(BENCH, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    importlib.import_module(f"bench.drivers.{traffic['driver']}")
    assert spec.load_limits(cell["name"])
    e2e = spec.metrics_of_cell(BENCH, cell, trace=False)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    layer = spec.metrics_of_cell(BENCH, cell, trace=True)
    assert layer
    for m in layer:
        assert callable(spec.load_reader(m["name"]))
        assert m["moves"] in names
    assert conf["name"] == cell["config"]


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(REPO, p))
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        ns = [e["name"] for e in BENCH[k]]
        assert len(ns) == len(set(ns))
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    cells = {c["name"] for c in BENCH["workloads"]}
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert "bound" not in m
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for c in BENCH["workloads"]:
        assert c["chips"] in (1, 4) and len(c["why"]) <= 200


def test_new_cell_and_metric_need_no_edit(tmp_path):
    """A cell on a new traffic mix and a new per-layer metric: only new
    files and new entries in BENCHMARK.json."""
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench")
    bench = json.loads(json.dumps(BENCH))
    old = bench["workloads"][0]
    traffic = spec.load_traffic(old["traffic"])
    traffic["why"] = "a new mix"
    (tmp_path / "bench" / "traffic" / "new.mix.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench" / "limits" / "new.cell.json").write_text(
        json.dumps({"limits": {"loss_gap": 1.0}}))
    (tmp_path / "bench" / "metrics" / "new.metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench["workloads"].append({"name": "new.cell", "config": old["config"],
                               "traffic": "new.mix", "chips": 1,
                               "why": "a new cell"})
    moved = spec.metrics_of_cell(BENCH, old, trace=False)[0]["name"]
    bench["per_layer"].append({"name": "new.metric", "unit": "ms",
                               "better": "lower", "source": "device_trace",
                               "layer": "step", "moves": moved,
                               "workloads": ["new.cell"]})
    for m in bench["end_to_end"]:
        if "workloads" in m and m["name"] == moved:
            m["workloads"].append("new.cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    root = str(tmp_path)
    b = spec.load_benchmark(root)
    cell = spec.find_cell(b, "new.cell")
    assert spec.load_traffic(cell["traffic"], root)["why"] == "a new mix"
    assert spec.load_limits("new.cell", root) == {"loss_gap": 1.0}
    layer = spec.metrics_of_cell(b, cell, trace=True)
    assert [m["name"] for m in layer] == ["new.metric"]
    assert spec.load_reader("new.metric", root)({}) == 42.0
    assert "new.metric" not in [m["name"] for m in spec.metrics_of_cell(
        b, old, trace=True)]


def test_reduced_key_must_be_listed(tmp_path):
    conf = spec.load_config(BENCH, BENCH["configs"][0]["name"])
    conf["published"]["num_hidden_layers"] -= 1
    with pytest.raises(spec.CellError, match="num_hidden_layers"):
        spec.arch_config(conf)
    conf["reduced"] = ["num_hidden_layers"]
    assert spec.arch_config(conf).n_layers == \
        conf["published"]["num_hidden_layers"]
