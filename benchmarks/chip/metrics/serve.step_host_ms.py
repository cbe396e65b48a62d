"""Scheduler (``serving/engine.py`` ``step_once``): each ``serve.step``
span of the window less its ``serve.step.wait``, mean over the steps
with live rows, in ms: the host's own time in a step.

Also prints to standard error where the window's device-idle time went,
summed by the innermost ``serve.*`` span covering it (``bench.submit``
and ``none`` are the other labels), and the step program's device time
per scope against its whole."""

import sys

from benchmarks.chip import spans


def read(ctx):
    if "window_steps" not in ctx:
        return None
    tr, (lo, hi), dev = ctx["trace"], ctx["window"], ctx["devices"][0]
    steps = spans.steps(tr, lo, hi)
    if not steps:
        return None
    waits = spans.spans(tr, "serve.step.wait", lo, hi)
    host = [s.dur - sum(w.dur for w in waits
                        if w.start >= s.start and w.end <= s.end)
            for s in steps]
    report(tr, lo, hi, dev)
    return spans.mean_ms(host)


def report(tr, lo, hi, dev):
    labels = spans.spans(tr) + tr.annotations("bench.submit")
    idle = spans.idle_by_span(tr.ops[dev], labels, lo, hi)
    total = sum(idle.values()) or 1.0
    print("serve.step_host_ms: device idle by span: " + ", ".join(
        f"{k} {v * 1e-6:.3f} ms ({100 * v / total:.1f}%)"
        for k, v in sorted(idle.items(), key=lambda kv: -kv[1])),
        file=sys.stderr)
    by = spans.device_ms_by_scope(tr, dev, lo, hi)
    runs = spans.step_runs(tr, dev, lo, hi)
    if by and runs:
        step = sum(r.dur for r in runs) / len(runs) * 1e-6
        print(f"serve.step_host_ms: jit_step_fn {step:.3f} ms a run; by "
              "scope: " + ", ".join(
                  f"{k} {v:.3f} ms ({100 * v / step:.1f}%)"
                  for k, v in sorted(by.items(), key=lambda kv: -kv[1])),
              file=sys.stderr)
