#!/usr/bin/env python3
"""``bench/run.py`` with ``--trace 1``, also breaking the traced window's
device time down by the program's named scopes.

    python bench/tools/scope_time.py <out.json> --workload <cell> \
        --seed <n> --seconds <s> --trace 1

Each operation that the trace names is looked up in the compiled step's
HLO text: its ``metadata={op_name=...}`` holds the ``jax.named_scope``
stack it was traced under (a fusion without one takes the scopes of the
computation it calls). Time goes to the first of ``SCOPES`` found in
that stack, forward and backward alike (``transpose(jvp(moe/route))``
counts as ``moe/route``), else to ``other``. Writes the breakdown, with
the largest operations of each scope, to ``<out.json>``.
"""
from __future__ import annotations

import json
import os
import re
import sys
from collections import defaultdict
from typing import Dict, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

SCOPES = ("moe/route", "moe/dispatch", "moe/experts", "moe/combine", "mla")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")


def scope_of(op_name: str) -> Optional[str]:
    for s in SCOPES:
        if re.search(r"(^|[/(])" + re.escape(s) + r"([/)]|$)", op_name):
            return s
    return None


def instruction_scopes(hlo: str) -> Dict[str, str]:
    """Instruction name -> scope, for every instruction of the module
    under one of ``SCOPES``."""
    own, calls, members = {}, {}, defaultdict(list)
    comp = None
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMP.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        name = m.group(1)
        members[comp].append(name)
        n = _OP_NAME.search(line)
        s = scope_of(n.group(1)) if n else None
        if s is not None:
            own[name] = s
        c = _CALLS.search(line)
        if c is not None:
            calls[name] = c.group(1)
    out = dict(own)
    for name, comp in calls.items():
        if name in out:
            continue
        found = [own[i] for i in members.get(comp, ()) if i in own]
        if found:
            out[name] = max(set(found), key=found.count)
    return out


def by_scope(reduced: dict, hlo: str, module: str, top: int = 6) -> dict:
    """Device time of the steady window of ``module`` by named scope."""
    from bench.trace import PARENTS, Trace, _HLO_NAME, base_name

    tr = Trace(reduced)
    win = tr.window(module)
    if win is None:
        raise ValueError(f"fewer than two executions of {module}")
    t0, t1, n = win
    scopes = instruction_scopes(hlo)
    ns: Dict[str, float] = defaultdict(float)
    ops: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for name, s, e in tr._intervals(0, t0, t1):
        if base_name(name) in PARENTS:
            continue
        m = _HLO_NAME.match(name)
        inst = m.group(1) if m else name
        scope = scopes.get(inst, "other")
        ns[scope] += e - s
        ops[scope][base_name(name)] += e - s
    total = sum(ns.values())
    return {
        "module": module, "steps": n, "window_s": (t1 - t0) / 1e9,
        "op_s": total / 1e9,
        "scopes": {k: {"s": v / 1e9, "share": v / total,
                       "top": [[o, t / 1e9] for o, t in sorted(
                           ops[k].items(), key=lambda kv: -kv[1])[:top]]}
                   for k, v in sorted(ns.items(), key=lambda kv: -kv[1])}}


def main() -> int:
    from bench import run, trace
    from repro.api import trainer

    dest, argv = sys.argv[1], sys.argv[2:]
    kept = {}
    inner_reduce, inner_compile = trace.reduce_xplane, \
        trainer.Trainer.compile_step

    def reduce(path):
        kept["reduced"] = inner_reduce(path)
        return kept["reduced"]

    def compile_step(self):
        compiled = inner_compile(self)
        kept["hlo"] = compiled.as_text()
        return compiled

    trace.reduce_xplane = reduce
    trainer.Trainer.compile_step = compile_step
    rc = run.main(argv)
    if rc == 0 and "reduced" in kept:
        from bench import device
        out = by_scope(kept["reduced"], kept["hlo"],
                       device.module_name(kept["hlo"]))
        os.makedirs(os.path.dirname(os.path.abspath(dest)), exist_ok=True)
        with open(dest, "w") as f:
            json.dump(out, f, indent=1)
        print(json.dumps(out), file=sys.stderr)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
