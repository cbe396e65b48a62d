"""Model step, attention (``models/lm.py`` ``_block_decode_paged``):
device time of the step program's operations under the ``attention``
scope (projections, paged KV write and gather, output projection), per
run of the step in the window, in ms."""

from benchmarks.chip import spans


def read(ctx):
    if "window_steps" not in ctx:
        return None
    lo, hi = ctx["window"]
    by = spans.device_ms_by_scope(ctx["trace"], ctx["devices"][0], lo, hi)
    return None if by is None else by.get("attention")
