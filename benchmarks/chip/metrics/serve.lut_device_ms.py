"""MLP's LUT matmul (``quant/int4.py`` ``approx_linear``, its kernel and
wrapper): device time of the step program's operations whose ``op_name``
path holds a ``lut.w<bits>`` scope (the kernel call, its padding and
tile extraction, the output conversion), per run of the step in the
window, in ms.  A program without the scope gives nothing to read."""

import re
from bisect import bisect_right

from benchmarks.chip import spans

LUT_SCOPE = re.compile(r"(^|/)lut\.w\d+(/|$)")


def read(ctx):
    if "window_steps" not in ctx:
        return None
    tr, (lo, hi), dev = ctx["trace"], ctx["window"], ctx["devices"][0]
    runs = spans.step_runs(tr, dev, lo, hi)
    ops = [e for e, path in spans.scoped_ops(tr, dev)
           if LUT_SCOPE.search(path)]
    if not runs or not ops:
        return None
    starts = [r.start for r in runs]
    total = 0.0
    for e in ops:
        i = bisect_right(starts, e.start) - 1
        if i >= 0 and e.end <= runs[i].end:
            total += e.dur
    return total / len(runs) * 1e-6
