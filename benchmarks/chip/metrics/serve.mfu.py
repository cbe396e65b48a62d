"""Whole model step: model FLOPs of the tokens the traced window
processed, over the window's length and the chip's bf16 peak, in %.
Emulation's extra work does not count."""


def read(ctx):
    if "window_steps" not in ctx:
        return None
    lo, hi = ctx["window"]
    flops = sum(s.flops for s in ctx["window_steps"])
    return 100.0 * flops / ((hi - lo) * 1e-9 * ctx["peak"]["bf16_flop_s"])
