"""Fixtures of the benchmark's CPU tests: a root holding the tiny cells of
``data/tiny`` beside the benchmark's own readers and architecture modules,
and a harness whose look for a chip is switched off (the rest of a run is
driven as on the chip)."""
import os
import shutil
import sys
from collections import Counter

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("benchroot")
    shutil.copytree(os.path.join(REPO, "tests", "bench", "data", "tiny"),
                    root, dirs_exist_ok=True)
    for sub in ("metrics", "archs"):
        shutil.copytree(os.path.join(REPO, "bench", sub),
                        root / "bench" / sub)
    return str(root)


@pytest.fixture
def no_chip(monkeypatch):
    """The harness minus its look for a chip: no TPU, kernel or HBM check,
    and no compile cache written from the test process."""
    from bench import device
    from repro.launch import compile_cache

    monkeypatch.setattr(device, "require_chip", lambda chips: None)
    monkeypatch.setattr(device, "refuse_interpret", lambda policy: None)
    monkeypatch.setattr(device, "require_kernels", lambda h, n: Counter())
    monkeypatch.setattr(device, "peak_bytes", lambda devices=None: 0)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
