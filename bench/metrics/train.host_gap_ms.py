"""Mean time on the device clock from the end of one execution of the
training step program to the start of the next: the host loop's own work
(loss sync, the step guard's eager passes, data) and idle time."""


def read(ctx):
    return ctx["trace"].gap_ms(ctx["module"])
