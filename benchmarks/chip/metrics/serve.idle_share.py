"""Device: the share of the traced window in which no operation ran on
the chip, in %."""

from benchmarks.chip import trace


def read(ctx):
    if "window_steps" not in ctx:
        return None
    lo, hi = ctx["window"]
    return 100.0 * trace.idle_share(ctx["trace"].ops[ctx["devices"][0]],
                                    lo, hi)
