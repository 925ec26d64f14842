#!/usr/bin/env python3
"""``bench/run.py`` with ``--trace 1``, keeping the reduced trace.

    python bench/tools/keep_trace.py <reduced.json> --workload <cell> \
        --seed <n> --seconds <s> --trace 1
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main() -> int:
    from bench import run, trace

    dest, argv = sys.argv[1], sys.argv[2:]
    inner = trace.reduce_xplane

    def keep(path):
        reduced = inner(path)
        os.makedirs(os.path.dirname(os.path.abspath(dest)), exist_ok=True)
        with open(dest, "w") as f:
            json.dump(reduced, f)
        return reduced

    trace.reduce_xplane = keep
    return run.main(argv)


if __name__ == "__main__":
    raise SystemExit(main())
