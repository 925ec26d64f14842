"""Mean device time of one execution of the training step program."""


def read(ctx):
    return ctx["trace"].exec_ms(ctx["module"])
