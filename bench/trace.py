"""From the profiler's trace to numbers.

:func:`reduce_xplane` keeps what the metrics need from an ``.xplane.pb``:
for each device plane the executions of whole programs ("XLA Modules")
and of single operations ("XLA Ops"), and the host spans that the harness
wrote around its own calls (``jax.profiler.TraceAnnotation``). The reduced
form is plain JSON, so a small recorded trace can be kept with the tests.
:class:`Trace` computes from it: the steady window of a named program,
busy time as the union of operation intervals, the gaps between
executions, and device time by operation name. Times are nanoseconds on
the profiler's clock.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

DISPATCH = "step dispatch"          # harness span around its program calls
HOST_SPANS = (DISPATCH,)
_SUFFIX = re.compile(r"\.\d+$")
_HLO_NAME = re.compile(r"^%([A-Za-z_0-9.\-]+) = ")
# ops that hold other ops (a layer scan's while loop): their time is their
# children's, so they are left out of time by operation
PARENTS = ("while", "conditional", "call")


@contextlib.contextmanager
def capture(log_dir: str):
    """Profile the enclosed block into ``log_dir`` (no Python tracer)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def latest_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def reduce_xplane(path: str) -> dict:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:") and "CPU" not in plane.name
        entry = {"plane": plane.name, "modules": [], "ops": []}
        for line in plane.lines:
            events = list(line.events)
            if is_dev and line.name == "XLA Modules":
                entry["modules"] = [[e.name, e.start_ns, e.duration_ns]
                                    for e in events]
            elif is_dev and line.name == "XLA Ops":
                entry["ops"] = [[e.name, e.start_ns, e.duration_ns]
                                for e in events]
            elif not is_dev:
                host += [[e.name, e.start_ns, e.duration_ns]
                         for e in events if e.name in HOST_SPANS]
        if is_dev:
            devices.append(entry)
    return {"devices": devices, "host": host}


def _union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def base_name(op: str) -> str:
    """``lora_fwd.12`` or the HLO text ``%lora_fwd.12 = bf16[...] ...`` that
    a TPU trace names its operations by -> ``lora_fwd``: one name for every
    call of a kernel."""
    m = _HLO_NAME.match(op)
    return _SUFFIX.sub("", m.group(1) if m else op)


class Trace:
    def __init__(self, reduced: dict):
        self.devices = [d for d in reduced["devices"]
                        if d["modules"] or d["ops"]]
        self.host = reduced.get("host", [])
        if not self.devices:
            raise ValueError("the trace holds no device operation")

    # ---------------------------------------------------------- programs
    def executions(self, module: str,
                   dev: int = 0) -> List[Tuple[float, float]]:
        """(start, end) of every execution of the program named ``module``."""
        out = [(s, s + d) for n, s, d in self.devices[dev]["modules"]
               if n.split("(")[0] == module]
        return sorted(out)

    def window(self, module: str,
               dev: int = 0) -> Optional[Tuple[float, float, int]]:
        """(start, end, n): from the start of the first execution of
        ``module`` to the start of the last, which holds n executions and
        the n gaps after them; None with fewer than two."""
        ex = self.executions(module, dev)
        if len(ex) < 2:
            return None
        return ex[0][0], ex[-1][0], len(ex) - 1

    def exec_ms(self, module: str, dev: int = 0) -> Optional[float]:
        win = self.window(module, dev)
        if win is None:
            return None
        ex = self.executions(module, dev)[:-1]
        return sum(e - s for s, e in ex) / len(ex) / 1e6

    def gap_ms(self, module: str, dev: int = 0) -> Optional[float]:
        """Mean time from the end of one execution to the start of the
        next; whatever runs between them counts."""
        ex = self.executions(module, dev)
        if len(ex) < 2:
            return None
        gaps = [b[0] - a[1] for a, b in zip(ex, ex[1:])]
        return sum(gaps) / len(gaps) / 1e6

    # ------------------------------------------------------------- device
    def _intervals(self, dev: int, t0: float, t1: float):
        d = self.devices[dev]
        src = d["ops"] or d["modules"]
        for n, s, dur in src:
            s2, e2 = max(s, t0), min(s + dur, t1)
            if e2 > s2:
                yield n, s2, e2

    def busy_ns(self, t0: float, t1: float) -> float:
        """Device-busy time in [t0, t1], averaged over the device planes."""
        tot = 0.0
        for dev in range(len(self.devices)):
            tot += sum(e - s for s, e in _union(
                (s, e) for _, s, e in self._intervals(dev, t0, t1)))
        return tot / len(self.devices)

    def op_events(self, t0: float, t1: float, names, dev: int = 0):
        """(base name, full name, ns) of the operations named ``names``."""
        return [(base_name(n), n, e - s)
                for n, s, e in self._intervals(dev, t0, t1)
                if base_name(n) in names]

    def op_ns(self, t0: float, t1: float, dev: int = 0) -> Dict[str, float]:
        """Device time by operation name (kernels under one name), loops
        that hold other operations left out."""
        out: Dict[str, float] = defaultdict(float)
        for n, s, e in self._intervals(dev, t0, t1):
            b = base_name(n)
            if b not in PARENTS:
                out[b] += e - s
        return dict(out)

    def op_calls(self, t0: float, t1: float, dev: int = 0) -> Dict[str, int]:
        out: Dict[str, int] = defaultdict(int)
        for n, s, e in self._intervals(dev, t0, t1):
            out[base_name(n)] += 1
        return dict(out)

    def idle_gaps(self, t0: float, t1: float, dev: int = 0, top: int = 10):
        """The longest idle gaps in [t0, t1], each named by the host span it
        falls in ("step dispatch": the harness was calling into the
        program) or "host loop" (anything else on the host)."""
        busy = _union((s, e) for _, s, e in self._intervals(dev, t0, t1))
        gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:top]:
            label = "host loop"
            for n, hs, hd in self.host:
                if hs < e and hs + hd > s and \
                        min(e, hs + hd) - max(s, hs) >= (e - s) / 2:
                    label = n
                    break
            out.append([label, (e - s) / 1e9])
        return out
