"""FLOPs the traced window's steps require (the configuration's
architecture module, ``train_flops_per_token``: no frozen weight
gradients, no recomputed work) over the window's seconds times the chip's
bf16 peak, in percent."""


def read(ctx):
    t0, t1, n = ctx["window"]
    tr = ctx["traffic"]
    tokens = n * tr["batch"] * tr["seq"]
    flops = tokens * ctx["arch"].train_flops_per_token(ctx["widths"],
                                                       tr["seq"])
    return 100.0 * flops / ((t1 - t0) / 1e9 * ctx["peaks"]["bf16_flops"])
