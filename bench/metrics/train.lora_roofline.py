"""Share of their roofline that the fused LoRA kernels reach in the traced
window: the least time their calls need over their summed device time, in
percent. Each call's need is that of its op at its logical shapes
(``bench/flops.py``; the larger of FLOPs over peak and bytes over
bandwidth): the target it serves is read from its operand and result
shapes in the trace, which may be padded, taking the call of the step
(the configuration's architecture module, ``lora_calls``: target, rows,
K, N) that fits them with the least padding, at that call's rows. A call
whose operands cannot be read is not guessed at: the metric is then not
read."""
import re

from bench.flops import lora_op
from bench.peaks import roofline_seconds

KERNELS = {"lora_fwd": "fwd", "lora_dx": "dx", "lora_dab": "dab"}
_SHAPE = re.compile(r"\[(\d+),(\d+)\]")


def _padded_kn(kind, text, rank):
    """(K, N) of a call as the kernel ran it (padded): fwd reads x [M, K]
    first and writes [M, N]; dx reads g [M, N] first and writes [M, K];
    dab writes dA [K, r] and dB [r, N]."""
    if " custom-call(" not in text:
        return None
    head, args = text.split(" custom-call(", 1)
    outs = [(int(a), int(b)) for a, b in _SHAPE.findall(head)]
    ins = [(int(a), int(b)) for a, b in _SHAPE.findall(args)]
    if not outs or not ins:
        return None
    if kind == "fwd":
        return ins[0][1], outs[0][1]
    if kind == "dx":
        return outs[0][1], ins[0][1]
    ks = [s[0] for s in outs if s[1] == rank]
    ns = [s[1] for s in outs if s[0] == rank]
    return (ks[0], ns[0]) if ks and ns else None


def _call(kind, text, calls, rank):
    """(M, K, N) of the step's call that fits a traced call's padded
    shapes with the least padding."""
    kn = _padded_kn(kind, text, rank)
    if kn is None:
        return None
    fits = [(m, k, n) for _, m, k, n in calls if k <= kn[0] and n <= kn[1]]
    return min(fits, key=lambda t: (kn[0] - t[1]) + (kn[1] - t[2])) \
        if fits else None


def read(ctx):
    t0, t1, _ = ctx["window"]
    w, tr = ctx["widths"], ctx["traffic"]
    events = ctx["trace"].op_events(t0, t1, KERNELS)
    if not events:
        return None
    calls = ctx["arch"].lora_calls(w, tr["batch"], tr["seq"])
    need = spent = 0.0
    for base, text, ns in events:
        mkn = _call(KERNELS[base], text, calls, w.rank)
        if mkn is None:
            return None
        need += roofline_seconds(*lora_op(KERNELS[base], *mkn, w.rank),
                                 ctx["kind"])
        spent += ns / 1e9
    return 100.0 * need / spent
