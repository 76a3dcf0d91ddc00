"""Model FLOP utilization of the traced window: the forward and backward
operations its steps require (``flops.train_flops``: no recomputation, an
MLM head over its masked positions only) over the window's length, over
the chips' bf16 peak."""


def read(ctx):
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * ctx["flops"] / ctx["window_s"] / peak
