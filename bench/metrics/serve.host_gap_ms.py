"""Mean time on the device clock from the end of one execution of the
decode step to the start of the next: admission, the eager argmax and its
host sync, and the per-row bookkeeping of the serve loop."""


def read(ctx):
    return ctx["trace"].gap_ms(ctx["module"])
