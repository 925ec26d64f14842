"""Trace spans on the profiler's clock, with Chrome-trace/Perfetto export.

Every span enters ``jax.profiler.TraceAnnotation(name)``: while a profiler
session runs, the span is a host event in the same trace as the device's
operations, so a gap on the device can be attributed to the host work
that covers it. With no session running an annotation costs under a
microsecond.

A :class:`Tracer` that is enabled also records each span as a complete
("ph": "X") event, ``(name, start_s, dur_s, depth)`` relative to its
epoch, for ``trace.json``. A disabled tracer records nothing: its
``span()`` is the bare annotation. When :class:`~repro.telemetry.Telemetry`
starts the profiler itself (``--profile on``) it moves the epoch to the
session's start (the midpoint of the call that starts it), so
``trace.json`` and the profile share an origin.

:data:`LOOP_SPANS` and :data:`SERVE_SPANS` name every span the training
and serving loops open; readers of a trace take the names from there.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import List

from jax.profiler import TraceAnnotation

#: the training loop's spans (``runtime/fault_tolerance.ResilientLoop``),
#: in loop order; each step attempt sits inside a
#: ``StepTraceAnnotation("train", step_num=step)``
LOOP_SPANS = ("train/restore", "train/data", "train/dispatch",
              "train/loss_sync", "train/guard", "train/watermark",
              "train/on_step", "train/checkpoint")
#: the serve loop's spans (``serve/loop.ContinuousBatcher.step``);
#: ``serve/reset_slot`` nests inside ``serve/admission``
SERVE_SPANS = ("serve/admission", "serve/reset_slot", "serve/dispatch",
               "serve/argmax_sync")


class Span:
    """One live span: a profiler annotation, recorded on the tracer at
    ``__exit__``."""

    __slots__ = ("tracer", "name", "t0", "depth", "_ann")

    def __init__(self, tracer: "Tracer", name: str):
        self.tracer = tracer
        self.name = name
        self.t0 = 0.0
        self.depth = 0
        self._ann = TraceAnnotation(name)

    def __enter__(self):
        self._ann.__enter__()
        tr = self.tracer
        self.depth = len(tr._stack)
        tr._stack.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self.t0
        tr = self.tracer
        if tr._stack and tr._stack[-1] is self:
            tr._stack.pop()
        tr.finished.append((self.name, self.t0 - tr.epoch, dur, self.depth))
        self._ann.__exit__(*exc)
        return False


class Tracer:
    """Collects finished spans as ``(name, start_s, dur_s, depth)`` tuples
    relative to the tracer's epoch (``time.perf_counter`` seconds)."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.epoch = time.perf_counter()
        self.finished: List[tuple] = []
        self._stack: List[Span] = []

    def span(self, name: str):
        if not self.enabled:
            return TraceAnnotation(name)
        return Span(self, name)

    # ------------------------------------------------------------ export
    def chrome_trace(self) -> List[dict]:
        """Chrome trace event format: complete events, µs timestamps."""
        pid = os.getpid()
        tid = threading.get_ident() % 10_000
        return [{"name": name, "ph": "X", "ts": round(start * 1e6, 1),
                 "dur": round(dur * 1e6, 1), "pid": pid, "tid": tid,
                 "args": {"depth": depth}}
                for name, start, dur, depth in self.finished]

    def save(self, path: str) -> str:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as fh:
            json.dump({"traceEvents": self.chrome_trace(),
                       "displayTimeUnit": "ms"}, fh)
        return path

    def totals(self) -> dict:
        """Per-name aggregate {count, total_s} — cheap summary for reports."""
        agg: dict = {}
        for name, _start, dur, _depth in self.finished:
            row = agg.setdefault(name, {"count": 0, "total_s": 0.0})
            row["count"] += 1
            row["total_s"] += dur
        return agg


# ----------------------------------------------------------- jax.profiler
def start_profiler(log_dir: str) -> bool:
    """Best-effort ``jax.profiler.start_trace``; returns success."""
    try:
        import jax
        os.makedirs(log_dir, exist_ok=True)
        jax.profiler.start_trace(log_dir)
        return True
    except Exception:  # pragma: no cover - platform dependent
        return False


def stop_profiler() -> bool:
    try:
        import jax
        jax.profiler.stop_trace()
        return True
    except Exception:  # pragma: no cover - platform dependent
        return False
