"""What the benchmark asks of the device, and what it reads from it.

The kernel-name regex, the ``peak_bytes_in_use`` reader, the refusal of a
backend that is not a TPU and of Pallas interpret mode are copied from
``chip_smoke.py``, which stays a separate pass/fail bring-up check.
"""
from __future__ import annotations

import re
from collections import Counter

_CUSTOM_CALL = re.compile(
    r"%([A-Za-z_0-9]+?)(?:\.\d+)? = [^\n]*custom_call_target="
    r"\"tpu_custom_call\"")
_MODULE = re.compile(r"^HloModule ([A-Za-z_0-9.\-]+)", re.M)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class RunFault(Exception):
    """The run is unsound: what it measured is not the program as asked."""


def require_chip(chips: int) -> None:
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise NoChip(f"JAX found no TPU (backend {backend!r})")
    if len(jax.devices()) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(jax.devices())}")


def refuse_interpret(policy) -> None:
    """Pallas interpret mode measures the interpreter, not the chip."""
    from repro.kernels import ops

    if policy.interpret or ops.pallas_interpret():
        raise RunFault("Pallas interpret mode would run")


def kernel_counts(hlo_text: str) -> Counter:
    """Pallas kernels in a compiled program, by ``pallas_call`` name."""
    return Counter(_CUSTOM_CALL.findall(hlo_text))


def module_name(hlo_text: str) -> str:
    m = _MODULE.search(hlo_text)
    if m is None:
        raise RunFault("compiled program has no HloModule line")
    return m.group(1)


def require_kernels(hlo_text: str, names) -> Counter:
    counts = kernel_counts(hlo_text)
    missing = [k for k in names if counts[k] < 1]
    if missing:
        raise RunFault(f"compiled program lacks kernels {missing}: "
                       f"{dict(counts)}")
    return counts


def peak_bytes(devices=None) -> int:
    """``peak_bytes_in_use`` of the fullest device (process peak so far)."""
    import jax

    peaks = []
    for dev in devices or jax.devices():
        stats = dev.memory_stats()
        if not stats or "peak_bytes_in_use" not in stats:
            raise RunFault(f"{dev} memory_stats() gives no peak_bytes_in_use")
        peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks)


def describe(count: int) -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": count}


class CompileCounter:
    """Counts backend compilations while ``active``: there should be none
    inside a measured window."""

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **kw):
        if self.active and name == COMPILE_EVENT:
            self.count += 1
