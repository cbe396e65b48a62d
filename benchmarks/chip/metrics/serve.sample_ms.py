"""Scheduler (``serving/engine.py`` ``step_once``): the
``serve.step.sample`` span (the argmax and its copy to the host), mean
over the window's steps, in ms."""

from benchmarks.chip import spans


def read(ctx):
    if "window_steps" not in ctx:
        return None
    lo, hi = ctx["window"]
    return spans.mean_ms(s.dur for s in spans.spans(
        ctx["trace"], "serve.step.sample", lo, hi))
