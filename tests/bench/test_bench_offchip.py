"""The harness never prints a result off the chip, nor where the program
is missing."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import spec

REPO = spec.ROOT
CELL = spec.load_benchmark()["workloads"][0]["name"]


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed",
         "2147483749", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0, proc.stdout
    for line in proc.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_exits_without_result_off_tpu():
    _no_result(_run(REPO))


def test_exits_without_result_where_the_program_is_missing(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in spec.load_benchmark()["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p)
    proc = _run(tmp_path)
    _no_result(proc)
    assert "program is not here" in proc.stderr
