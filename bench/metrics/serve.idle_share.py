"""Share of the traced steady window (first to last start of the decode
step) in which no operation ran on the device, in percent."""


def read(ctx):
    t0, t1, _ = ctx["window"]
    return 100.0 * (1.0 - ctx["trace"].busy_ns(t0, t1) / (t1 - t0))
