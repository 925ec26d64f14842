"""Share of their roofline that the flash attention kernels reach in the
traced window: forward calls at the causal forward's need, each
``flash_dq``/``flash_dkv`` pair at the backward's, as the configuration's
architecture module counts them (``flash_ops``, from ``bench/flops.py``),
over the kernels' summed device time, in percent."""
from bench.peaks import roofline_seconds


def read(ctx):
    t0, t1, _ = ctx["window"]
    w, tr = ctx["widths"], ctx["traffic"]
    calls = ctx["trace"].op_calls(t0, t1)
    times = ctx["trace"].op_ns(t0, t1)
    nf, nq, nk = (calls.get(k, 0) for k in ("flash_fwd", "flash_dq",
                                            "flash_dkv"))
    if not (nf and nq and nq == nk):
        return None
    fwd, bwd = ctx["arch"].flash_ops(w, tr["batch"], tr["seq"])
    need = (nf * roofline_seconds(*fwd, ctx["kind"])
            + nq * roofline_seconds(*bwd, ctx["kind"]))
    spent = sum(times[k] for k in ("flash_fwd", "flash_dq", "flash_dkv"))
    return 100.0 * need / (spent / 1e9)
