"""Scheduler (``serving/engine.py`` ``step_once``): the harness's
annotation around each ``step_once``, less the device-busy time inside
it, mean over the window's steps, in ms."""

from benchmarks.chip import trace


def read(ctx):
    if "window_steps" not in ctx:
        return None
    lo, hi = ctx["window"]
    ops = ctx["trace"].ops[ctx["devices"][0]]
    steps = [a for a in ctx["trace"].annotations("bench.step_once")
             if a.start >= lo and a.end <= hi]
    if not steps:
        return None
    spans = trace.union(ops, lo, hi)
    host = [a.dur - trace.covered(spans, a.start, a.end) for a in steps]
    return sum(host) / len(host) * 1e-6
