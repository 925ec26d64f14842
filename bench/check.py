"""The comparison that decides ``correct``: numbers read from the timed
path against the plain reference, each beside its limit
(``bench/limits/<cell>.json``, with the readings it was set from)."""
from __future__ import annotations

import sys
from typing import Dict, List

import numpy as np

# a leaf whose reference gradient is under this share of the median
# leaf's moves by round-off alone and is left out of a norm comparison
NEGLIGIBLE = 1e-3


def _norms(tree: Dict[str, Dict[str, np.ndarray]]) -> Dict[str, float]:
    return {f"{t}.{k}": float(np.linalg.norm(np.asarray(v, np.float64)))
            for t, d in sorted(tree.items()) for k, v in sorted(d.items())}


def _diff(a, b):
    return {t: {k: np.asarray(a[t][k], np.float64)
                - np.asarray(b[t][k], np.float64) for k in a[t]} for t in a}


def norm_gap(prog: Dict[str, float], ref: Dict[str, float],
             counted: List[str]) -> float:
    """Worst leaf's gap between the program's norm and the reference's,
    against the larger of that leaf's reference norm and the median's."""
    med = float(np.median([ref[k] for k in counted]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in counted)


def diff_ratio(prog, ref, counted: List[str]) -> float:
    """Worst leaf's norm of the difference from the reference, against the
    larger of that leaf's reference norm and the median's: first order in
    an error that the gap of norms sees only to second order."""
    rn = _norms(ref)
    dn = _norms(_diff(prog, ref))
    med = float(np.median([rn[k] for k in counted]))
    return max(dn[k] / max(rn[k], med) for k in counted)


def train_numbers(*, lr, losses, ref_losses, p0, p1, p3, ref_grads,
                  ref_p3) -> Dict[str, float]:
    """Training: the first three steps' losses, the first gradient as the
    optimizer got it ((P0 - P1) / lr, read from the program's state after
    one step), and the LoRA factors' change over three steps.

    ``p*`` are LoRA trees ``{target: {"a", "b"}}`` (host arrays),
    ``ref_grads`` the reference's gradient at each of the three steps."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses, ref_losses))
    g_tree = {t: {k: v / lr for k, v in d.items()}
              for t, d in _diff(p0, p1).items()}
    g_prog = _norms(g_tree)
    g_ref = [_norms(g) for g in ref_grads]
    med0 = float(np.median(list(g_ref[0].values())))
    first = [k for k, v in g_ref[0].items() if v >= NEGLIGIBLE * med0]
    gmax = {k: max(g[k] for g in g_ref) for k in g_ref[0]}
    medx = float(np.median(list(gmax.values())))
    moved = [k for k, v in gmax.items() if v >= NEGLIGIBLE * medx]
    d_prog = _norms(_diff(p3, p0))
    d_ref = _norms(_diff(ref_p3, p0))
    return {
        "loss_gap": loss_gap,
        "grad_norm_gap": norm_gap(g_prog, g_ref[0], first),
        "change_norm_gap": norm_gap(d_prog, d_ref, moved),
        "grad_diff": diff_ratio(g_tree, ref_grads[0], first),
        "change_diff": diff_ratio(_diff(p3, p0), _diff(ref_p3, p0), moved),
        "leaves_left_out": float(len(g_ref[0]) - len(moved)),
    }


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """``correct`` and the checks: every limited number at or under its
    limit. Numbers without a limit are readings only."""
    checks = {k: {"value": numbers[k], "limit": lim}
              for k, lim in limits.items()}
    correct = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    return correct, checks


def print_checks(checks: dict, stream=sys.stderr) -> None:
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=stream, flush=True)
