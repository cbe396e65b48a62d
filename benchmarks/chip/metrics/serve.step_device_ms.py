"""Model step (``models/lm.py`` ``decode_step_paged``): device time of
each run of the step program in the trace, mean, in ms."""

from benchmarks.chip import trace

STEP = r"^jit_step_fn\("


def read(ctx):
    if "window_steps" not in ctx:
        return None
    lo, hi = ctx["window"]
    runs = trace.matching(ctx["trace"].modules.get(ctx["devices"][0], []),
                          STEP, lo, hi)
    if not runs:
        return None
    return sum(e.dur for e in runs) / len(runs) * 1e-6
