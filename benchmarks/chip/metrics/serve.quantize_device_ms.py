"""Model step, MLP quantization (``quant/int4.py`` ``approx_linear``):
device time of the step program's operations under the ``quantize``
scope (activation and weight quantization, bias correction, rescale),
per run of the step in the window, in ms."""

from benchmarks.chip import spans


def read(ctx):
    if "window_steps" not in ctx:
        return None
    lo, hi = ctx["window"]
    by = spans.device_ms_by_scope(ctx["trace"], ctx["devices"][0], lo, hi)
    return None if by is None else by.get("quantize")
