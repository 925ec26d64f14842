"""Mean device time of one execution of the decode step program."""


def read(ctx):
    return ctx["trace"].exec_ms(ctx["module"])
