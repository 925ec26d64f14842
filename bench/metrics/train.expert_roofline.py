"""Share of their roofline that the routed-expert kernels (``expert_fwd``,
``expert_dx``, ``expert_dab``) reach in the traced window: the least time
their calls need over their summed device time, in percent.

Each traced call is matched, by the K and N its operands and results show
(padded), to the expert linear of the step that fits them with the least
padding (the configuration's architecture module, ``expert_calls``:
target, rows, K, N, experts held). Its need is that call's op at those
rows (``expert_op``: one ``bench/flops.lora_op`` per held expert, the rows
split evenly, each expert's weights read once), the larger of FLOPs over
peak and bytes over bandwidth. The rows are the expected share of the
routing (every held expert at ``top_k / router_experts`` of the tokens),
not the rows a step routed. A call whose shapes cannot be read is not
guessed at: the metric is then not read."""
import re

from bench.peaks import roofline_seconds

KERNELS = {"expert_fwd": "fwd", "expert_dx": "dx", "expert_dab": "dab"}
_SHAPE = re.compile(r"\[([\d,]+)\]")


def _shapes(text):
    return [tuple(int(v) for v in s.split(",")) for s in _SHAPE.findall(text)]


def _padded_kn(kind, text):
    """(K, N) of a call as the kernel ran it: fwd reads x [M, K] and writes
    [M, N]; dx reads g [M, N] and writes [M, K]; dab writes dA [E, K, r]
    and dB [E, r, N]."""
    if " custom-call(" not in text:
        return None
    head, args = text.split(" custom-call(", 1)
    outs = _shapes(head)
    rows = [s for s in _shapes(args) if len(s) == 2]
    if kind == "dab":
        cubes = [s for s in outs if len(s) == 3]
        return (cubes[0][1], cubes[1][2]) if len(cubes) == 2 else None
    if not outs or not rows:
        return None
    if kind == "fwd":
        return rows[0][1], outs[0][-1]
    return outs[0][-1], rows[0][1]


def read(ctx):
    t0, t1, _ = ctx["window"]
    w, tr = ctx["widths"], ctx["traffic"]
    events = ctx["trace"].op_events(t0, t1, KERNELS)
    if not events:
        return None
    arch = ctx["arch"]
    calls = arch.expert_calls(w, tr["batch"], tr["seq"])
    need = spent = 0.0
    for base, text, ns in events:
        kn = _padded_kn(KERNELS[base], text)
        if kn is None:
            return None
        fits = [c for c in calls if c[2] <= kn[0] and c[3] <= kn[1]]
        if not fits:
            return None
        _, m, k, n, groups = min(
            fits, key=lambda c: (kn[0] - c[2]) + (kn[1] - c[3]))
        need += roofline_seconds(
            *arch.expert_op(KERNELS[base], m, k, n, w.rank, groups),
            ctx["kind"])
        spent += ns / 1e9
    return 100.0 * need / spent
