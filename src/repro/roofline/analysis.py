"""Roofline-term derivation from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), in seconds:

    compute    = HLO_FLOPs / (chips × peak_FLOP/s)
    memory     = HLO_bytes / (chips × HBM_bw)
    collective = collective_bytes / (chips × link_bw)

``cost_analysis()`` provides FLOPs / bytes-accessed; collective bytes are
parsed from the compiled HLO text (``all-gather`` / ``all-reduce`` /
``reduce-scatter`` / ``all-to-all`` / ``collective-permute``) by
``hlo_parse``: the payload side of each op (AG output, RS input, AR
either; every element of a combined collective's tuple).

Hardware model: TPU v5e — 197 TFLOP/s bf16/chip, 819 GB/s HBM, ~50 GB/s/link
ICI.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional

from repro.roofline.hlo_parse import _COLLECTIVES, HloModule

HW = {
    "peak_flops": 197e12,   # bf16 / chip
    "hbm_bw": 819e9,        # bytes/s / chip
    "ici_bw": 50e9,         # bytes/s / link
}


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Total payload bytes per collective kind in an HLO module: every
    collective of every computation, counted once (no while-trip scaling),
    with ``hlo_parse``'s payload convention
    (:meth:`~repro.roofline.hlo_parse.HloModule.collective_payload`)."""
    mod = HloModule(hlo_text)
    out = {k: 0 for k in _COLLECTIVES}
    for ops in mod.computations.values():
        for op in ops:
            kind = op.opcode.replace("-start", "")
            if kind in out:
                out[kind] += mod.collective_payload(op)
    return out


def predicted_grad_sync_bytes(n_trainable: int, mesh_axes: Dict[str, int],
                              dtype_bytes: int = 4) -> int:
    """Analytic lower bound on the per-device data-parallel gradient-sync
    payload of one train step, for checking compiled HLO (via
    :func:`collective_bytes`) against the roofline model — the emulated-fleet
    suite (tests/multihost/) asserts measured >= predicted.

    Every trainable element is reduced over the DP axes exactly once per
    step, and a device holds at least ``1/model`` of the elements (model-
    sharded LoRA factors), so::

        bytes >= n_trainable * dtype_bytes / model    (when dp > 1)

    With a single data shard there is nothing to sync (0).

    Caller picks what to count: when checking *static* HLO text, pass the
    per-loop-body element count (one layer slice of leaves that live under
    a scanned block stack — the compiled program contains that body once
    however many times it runs) in the gradient's *compute* dtype.
    """
    dp = 1
    for a in ("pod", "data"):
        dp *= mesh_axes.get(a, 1)
    if dp <= 1:
        return 0
    return (n_trainable * dtype_bytes) // max(mesh_axes.get("model", 1), 1)


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE); D = processed tokens.

    For decode shapes D = global_batch (one token per sequence)."""
    n_params = cfg.n_active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_params * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_params * tokens  # forward only
    return 2.0 * n_params * shape.global_batch  # decode: 1 token/seq


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    coll_bytes: float
    coll_breakdown: Dict[str, int] = field(default_factory=dict)
    model_flops_: float = 0.0
    mem_per_device: Optional[dict] = None

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / (self.chips * HW["peak_flops"])

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / (self.chips * HW["hbm_bw"])

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / (self.chips * HW["ici_bw"])

    @property
    def dominant(self) -> str:
        ts = {"compute": self.t_compute, "memory": self.t_memory,
              "collective": self.t_collective}
        return max(ts, key=ts.get)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops_ / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the step spent at the limiting roofline doing useful
        work: MODEL_FLOPS-time / max(term)."""
        tmax = max(self.t_compute, self.t_memory, self.t_collective)
        t_useful = self.model_flops_ / (self.chips * HW["peak_flops"])
        return t_useful / tmax if tmax else 0.0

    def row(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective, "dominant": self.dominant,
            "model_flops": self.model_flops_, "hlo_flops": self.hlo_flops,
            "useful_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def analyze(cfg, shape, mesh_name: str, chips: int, compiled,
            hlo_text: Optional[str] = None) -> RooflineReport:
    """Terms are PER-DEVICE (the compiled module is the SPMD-partitioned
    program), matching  total/(chips×peak)  in the brief's formulas.

    FLOPs/bytes come from the trip-count-aware HLO analyzer
    (``hlo_parse``), because XLA's ``cost_analysis()`` counts scan bodies
    once (~L× under-report for scan-over-layers models) — both are recorded.
    """
    from repro.roofline.hlo_parse import analyze_text

    ca = compiled.cost_analysis()
    if isinstance(ca, list):  # older jax returns [dict]
        ca = ca[0]
    text = hlo_text if hlo_text is not None else compiled.as_text()
    tot = analyze_text(text)
    flops = tot.flops * chips            # whole-job totals; terms divide back
    nbytes = tot.bytes * chips
    coll = {k: v * chips for k, v in tot.coll.items()}
    mem = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = {
                "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
                "output_bytes": getattr(ma, "output_size_in_bytes", None),
                "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
                "peak_bytes": getattr(ma, "peak_memory_in_bytes", None),
            }
    except Exception:
        pass
    rep = RooflineReport(
        arch=cfg.name, shape=shape.name, mesh=mesh_name, chips=chips,
        hlo_flops=flops, hlo_bytes=nbytes, coll_bytes=float(sum(coll.values())),
        coll_breakdown=coll, model_flops_=model_flops(cfg, shape),
        mem_per_device=mem)
    rep.xla_cost_analysis = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
    }
    return rep
