"""Block-size selection for the Pallas kernels.

Two layers:

1. **Heuristic table** (:func:`choose_blocks`) — shape/dtype-keyed rules that
   pick MXU-friendly block sizes without running anything. This is what the
   dispatch layer (``ops.py``) uses by default; it is deterministic at trace
   time so jit caches stay stable.
2. **Measured autotune** (:func:`autotune`) — optional: time a candidate
   sweep for an op instance and cache the winner, keyed by
   ``(op, dims, dtype, backend)``. The cache is consulted by
   :func:`choose_blocks` before the heuristics, and persists to JSON
   (``save_cache``/``load_cache``). Benchmarks run it explicitly; training
   never blocks on measurement.

Persisted caches, loaded lazily on first use (resolving the backend at
import would force JAX runtime initialization as an import side effect) in
priority order (later wins):

1. the **checked-in per-backend-generation cache**
   ``kernels/autotune_cache/<backend_generation()>.json`` (e.g. ``cpu.json``
   for the interpret-mode CI runs, ``tpu-v5p.json`` measured once per chip
   generation and committed);
2. an explicit ``REPRO_AUTOTUNE_CACHE=<path>`` override.

``benchmarks/kernels.py`` run with ``REPRO_AUTOTUNE=1`` re-measures and
rewrites the current backend's checked-in file via :func:`save_cache`.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, Iterable, Optional

import jax
import jax.numpy as jnp

from repro.kernels.tiling import ceil_to
from repro.telemetry.metrics import CounterGroup

# key -> {"bm": ..., ...}
_CACHE: Dict[str, Dict[str, int]] = {}

#: module-global cache/sweep traffic counters ("autotune.*"). Module-level
#: (not run-scoped) because kernel dispatch cannot depend on a run object;
#: an enabled Telemetry adopts this group into its registry, so TrainResult
#: metric snapshots report hit/miss/sweep traffic per run segment.
COUNTERS = CounterGroup(
    "autotune", ("cache_hit", "cache_miss", "sweeps", "sweep_candidates"))


def cache_stats() -> Dict[str, int]:
    """Plain-dict view of the traffic counters (benchmarks, tests)."""
    return dict(COUNTERS)

#: per-backend-generation measured caches checked into the repo
CACHE_DIR = os.path.join(os.path.dirname(__file__), "autotune_cache")


def _active_mesh():
    """The physical mesh of the enclosing ``with mesh:`` context, or None.
    Cheap attribute reads — never initializes a backend by itself."""
    from jax.interpreters import pxla
    mesh = pxla.thread_resources.env.physical_mesh
    if mesh is None or not mesh.axis_names or mesh.size <= 1:
        return None
    return mesh


def _local_dims(dims: Dict[str, int], axis_sizes: Dict[str, int]) -> Dict[str, int]:
    """Per-shard dims under a mesh: the token-row dim ``M`` (lora/rmsnorm
    kernels' B·N rows) is split over the data-parallel axes, and the flash
    seq dims over ``model`` when Megatron-SP divides them. Dims that don't
    divide stay global (GSPMD keeps them unsplit or pads — the kernel still
    sees the global block problem). Pure function of its arguments so it is
    testable without a live mesh."""
    dp = 1
    for a in ("pod", "data"):
        dp *= axis_sizes.get(a, 1)
    mp = axis_sizes.get("model", 1)
    out = dict(dims)
    if dp > 1 and "M" in out and out["M"] % dp == 0:
        out["M"] = out["M"] // dp
    for k in ("Nq", "Nk"):
        if mp > 1 and k in out and out[k] % mp == 0:
            out[k] = out[k] // mp
    return out


def _key(op: str, dims: Dict[str, int], dtype, mesh=None) -> str:
    """Cache key: ``op|dims|dtype|backend`` unsharded (the historical format,
    so committed caches keep hitting), with ``|mesh=<axes>`` inserted before
    the backend inside a mesh context — block-size winners depend on the
    per-shard *local* problem, so sharded runs must not reuse (or clobber)
    single-device entries. Keys always end in ``|<backend>``: ``save_cache``
    filters on that suffix. ``mesh`` overrides the ambient-context lookup
    (tests use an AbstractMesh, which has geometry but no ``with`` support
    on this JAX version)."""
    mesh = mesh if mesh is not None else _active_mesh()
    tag = ""
    if mesh is not None:
        sizes = {a: int(mesh.shape[a]) for a in mesh.axis_names}
        dims = _local_dims(dims, sizes)
        tag = "mesh=" + "x".join(f"{a}{n}" for a, n in sizes.items()) + "|"
    d = "/".join(f"{k}={v}" for k, v in sorted(dims.items()))
    return f"{op}|{d}|{jnp.dtype(dtype).name}|{tag}{jax.default_backend()}"


def backend_generation() -> str:
    """Cache-file name for the current accelerator generation: block-size
    winners transfer within a generation (same MXU/VMEM geometry) but not
    across, so e.g. ``tpu-v5p`` and ``tpu-v4`` get separate files; every
    non-TPU backend runs the interpreter and shares one file per platform."""
    if jax.default_backend() == "tpu":
        kind = jax.devices()[0].device_kind       # e.g. "TPU v5p"
        return kind.lower().replace(" ", "-")
    return jax.default_backend()                  # "cpu" / "gpu"


def builtin_cache_path() -> str:
    return os.path.join(CACHE_DIR, backend_generation() + ".json")


# ---------------------------------------------------------------------------
# heuristics
# ---------------------------------------------------------------------------

# Soft VMEM budget per resident block set (bytes). Real VMEM is ~16 MB/core;
# leave room for double buffering and scratch.
_VMEM_BUDGET = 4 << 20


def _pick(n: int, tiers: Iterable[int]) -> int:
    """Largest tier that n fills completely; 128 floor otherwise."""
    for t in tiers:
        if n >= t:
            return t
    return 128


def _matmul_blocks(M: int, K: int, N: int, dtype,
                   w_itemsize: Optional[float] = None) -> Dict[str, int]:
    """``w_itemsize``: bytes/elem of the weight tile when it differs from
    the activation dtype (int8-W0 kernels pass 1, packed int4/nf4 kernels
    0.5 — the smaller tile admits larger K/N blocks for the same VMEM
    residency)."""
    bm = _pick(M, (256,))
    bn = _pick(N, (512, 256))
    bk = _pick(K, (512, 256))
    # shrink until x/w/acc blocks fit the soft budget
    item = jnp.dtype(dtype).itemsize
    w_item = item if w_itemsize is None else w_itemsize
    while (bm * bk * item + bk * bn * w_item + bm * bn * 4) > _VMEM_BUDGET \
            and max(bm, bn, bk) > 128:
        if bk >= bn and bk > 128:
            bk //= 2
        elif bn > 128:
            bn //= 2
        else:
            bm //= 2
    return {"bm": bm, "bn": bn, "bk": bk}


def _heuristic(op: str, dims: Dict[str, int], dtype) -> Dict[str, int]:
    if op in ("lora_fused", "lora_dx"):
        return _matmul_blocks(dims["M"], dims["K"], dims["N"], dtype)
    if op in ("lora_fused_q", "lora_dx_q"):
        return _matmul_blocks(dims["M"], dims["K"], dims["N"], dtype,
                              w_itemsize=1)
    if op in ("lora_fused_q4", "lora_dx_q4"):
        # two nibbles per byte: the W0 tile costs half an int8 tile in VMEM
        # (the unpacked [bk, bn] value tile is transient VPU output)
        return _matmul_blocks(dims["M"], dims["K"], dims["N"], dtype,
                              w_itemsize=0.5)
    if op == "lora_dab":
        # grid is rows-only; x[bm,K] and g[bm,N] are both resident
        item = jnp.dtype(dtype).itemsize
        bm = _pick(dims["M"], (512, 256))
        K, N = dims["K"], dims["N"]
        while bm > 128 and bm * (ceil_to(K, 128) + ceil_to(N, 128)) * item \
                > _VMEM_BUDGET:
            bm //= 2
        return {"bm": bm}
    if op in ("lora_grouped", "lora_grouped_dx",
              "lora_grouped_q", "lora_grouped_dx_q",
              "lora_grouped_q4", "lora_grouped_dx_q4"):
        # bm is layout-determined (the per-group row-tile granularity chosen
        # by the dispatcher before packing); only bn/bk are tunable here.
        w_item = 0.5 if op.endswith("_q4") else 1 if op.endswith("_q") \
            else None
        blk = _matmul_blocks(dims["M"], dims["K"], dims["N"], dtype,
                             w_itemsize=w_item)
        blk.pop("bm", None)
        return blk
    if op == "lora_grouped_dab":
        # same residency shape as lora_dab (x[bm,K] + g[bm,N] resident) but
        # bm is fixed by the group layout, so nothing to choose.
        return {}
    if op in ("rmsnorm", "rmsnorm_bwd"):
        # the backward holds x, g and dx blocks (double-buffered) and its
        # f32 temporaries: ~19 MB of scoped VMEM at 512 rows of 2048 on a
        # v5e (16 MB allowed), about 18 bytes an element, so its rows get
        # a budget of their own
        d = max(dims["d"], 1)
        per_row = d * (4 if op == "rmsnorm" else 20)
        budget = _VMEM_BUDGET if op == "rmsnorm" else 14 << 20
        bm = _pick(dims["M"], (512, 256))
        while bm > 128 and bm * per_row > budget:
            bm //= 2
        return {"bm": bm}
    if op == "flash":
        D = dims.get("D", 128)
        bq = _pick(dims["Nq"], (512, 256) if D <= 64 else (256,))
        bk = _pick(dims["Nk"], (512, 256) if D <= 64 else (256,))
        return {"bq": bq, "bk": bk}
    raise ValueError(f"unknown op {op!r}")


def choose_blocks(op: str, dtype=jnp.float32, **dims: int) -> Dict[str, int]:
    """Measured-cache lookup, falling back to the heuristic table."""
    _ensure_loaded()
    hit = _CACHE.get(_key(op, dims, dtype))
    if hit is not None:
        COUNTERS["cache_hit"] += 1
        return dict(hit)
    COUNTERS["cache_miss"] += 1
    return _heuristic(op, dims, dtype)


# ---------------------------------------------------------------------------
# measured autotune
# ---------------------------------------------------------------------------


def _time_once(fn: Callable[[], object]) -> float:
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    return time.perf_counter() - t0


def autotune(op: str, run: Callable[[Dict[str, int]], object], *,
             candidates: Iterable[Dict[str, int]],
             dtype=jnp.float32, repeats: int = 3,
             **dims: int) -> Dict[str, int]:
    """Measure ``run(blocks)`` for each candidate, cache and return the best.

    ``run`` must execute the kernel with the given block sizes and return a
    JAX value (used for ``block_until_ready``). Candidates that fail to
    compile/execute (e.g. VMEM overflow on real TPUs) are skipped.

    Timing discipline: the compile call is synced and never timed, and the
    *first timed* iteration is discarded too (dispatch/transfer warm-up) —
    otherwise a candidate can be crowned or buried on compile noise.
    """
    _ensure_loaded()
    COUNTERS["sweeps"] += 1
    best, best_t = None, float("inf")
    for blocks in candidates:
        COUNTERS["sweep_candidates"] += 1
        try:
            jax.block_until_ready(run(blocks))       # compile — never timed
            times = [_time_once(lambda: run(blocks))
                     for _ in range(repeats + 1)]
        except Exception:
            continue
        t = min(times[1:])                           # drop warm-up iteration
        if t < best_t:
            best, best_t = dict(blocks), t
    if best is None:
        best = _heuristic(op, dims, dtype)
    _CACHE[_key(op, dims, dtype)] = dict(best)
    return best


def load_cache(path: str) -> int:
    """Merge a JSON cache file; returns number of entries loaded."""
    with open(path) as f:
        data = json.load(f)
    _CACHE.update({k: {kk: int(vv) for kk, vv in v.items()}
                   for k, v in data.items()})
    return len(data)


def save_cache(path: Optional[str] = None) -> str:
    """Persist the measured cache; default target is the checked-in
    per-backend-generation file (``autotune_cache/<backend>.json``).

    Only the *current* backend's entries are written (keys end in
    ``|<backend>``): the merged in-memory cache may also hold entries
    loaded from other generations' files or a ``REPRO_AUTOTUNE_CACHE``
    override, and those must not leak into this backend's committed file.
    """
    path = path or builtin_cache_path()
    suffix = f"|{jax.default_backend()}"
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump({k: v for k, v in _CACHE.items() if k.endswith(suffix)},
                  f, indent=1, sort_keys=True)
        f.write("\n")
    return path


_LOADED = False


def _ensure_loaded() -> None:
    """First-use loads: checked-in per-backend cache first, then the
    ``REPRO_AUTOTUNE_CACHE`` override (its entries win the merge). Lazy so
    that importing the package never initializes the JAX runtime (the
    backend name is part of the cache-file name)."""
    global _LOADED
    if _LOADED:
        return
    _LOADED = True
    for path in (builtin_cache_path(),
                 os.environ.get("REPRO_AUTOTUNE_CACHE")):
        if path and os.path.exists(path):
            try:
                load_cache(path)
            except Exception:
                pass
