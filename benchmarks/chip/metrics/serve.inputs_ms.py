"""Scheduler (``serving/engine.py`` ``step_once``): the
``serve.step.inputs`` span (page tables, the host arrays and their
transfers), mean over the window's steps, in ms."""

from benchmarks.chip import spans


def read(ctx):
    if "window_steps" not in ctx:
        return None
    lo, hi = ctx["window"]
    return spans.mean_ms(s.dur for s in spans.spans(
        ctx["trace"], "serve.step.inputs", lo, hi))
