"""Open-loop serving traffic from a traffic file and ``--seed``.

Every seed gets the same work in another order: the request sizes are
fixed quantiles of the file's log-normal distributions, the gaps between
arrivals fixed quantiles of the exponential at the file's rate (a Poisson
process), and the tenants fixed Zipf shares; the seed permutes each of
them and draws the prompt tokens. So runs differ by arrangement, not by
the amount of work, and a run's spread is the system's, not the draw's.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    due: float           # seconds after the schedule starts
    tenant: int
    prompt: tuple
    max_new: int


def _lognormal_quantiles(n: int, spec: dict) -> np.ndarray:
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def zipf_counts(n: int, tenants: int, s: float) -> np.ndarray:
    """Requests per tenant, Zipf(s) shares of ``n`` rounded to sum to n."""
    p = 1.0 / np.arange(1, tenants + 1) ** s
    p /= p.sum()
    counts = np.floor(p * n).astype(int)
    rest = n - counts.sum()
    order = np.argsort(-(p * n - counts))
    counts[order[:rest]] += 1
    return counts


def schedule(traffic: dict, seconds: float, seed: int,
             vocab: int) -> List[Arrival]:
    """Arrivals for ``seconds`` of traffic at the file's rate."""
    rate = traffic["rate_per_s"]
    n = int(math.ceil(rate * seconds))
    rng = np.random.default_rng(seed)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    due = np.cumsum(rng.permutation(gaps))
    prompts = rng.permutation(_lognormal_quantiles(n, traffic["prompt"]))
    outs = rng.permutation(_lognormal_quantiles(n, traffic["output"]))
    tenants = rng.permutation(np.repeat(
        np.arange(traffic["tenants"]),
        zipf_counts(n, traffic["tenants"], traffic["zipf_s"])))
    out = []
    for i in range(n):
        toks = rng.integers(1, vocab, int(prompts[i]))
        out.append(Arrival(float(due[i]), int(tenants[i]),
                           tuple(int(t) for t in toks), int(outs[i])))
    return out
