"""LUT matmul kernel (``kernels/approx_matmul.py``): the least time its
calls could take on the chip, over the kernel time in the trace, in %.

The least time of one call is the larger of its operations over the int8
peak and its bytes (operand codes at their width, int32 outputs) over
HBM bandwidth, at the live rows of that step, not the padded block."""

import sys

from benchmarks.chip import trace, work

KERNEL = r"^approx_matmul_pallas$"


def read(ctx):
    if "window_steps" not in ctx:
        return None
    lo, hi = ctx["window"]
    calls = trace.matching(ctx["trace"].ops[ctx["devices"][0]], KERNEL,
                           lo, hi)
    if not calls:
        return None
    d, peak = ctx["dims"], ctx["peak"]
    floor, bounds = 0.0, set()
    for step in ctx["window_steps"]:
        for k, n in work.mlp_shapes(d.d_model, d.d_ff):
            t, bound = work.lut_matmul_floor(step.rows, k, n, d.lut_bits,
                                             peak)
            floor += t * d.layers
            bounds.add(bound)
    kernel_s = sum(e.dur for e in calls) * 1e-9
    print(f"lut_matmul_roofline: {len(calls)} kernel event(s), "
          f"{kernel_s:.6f} s; floor {floor:.6f} s, {'/'.join(sorted(bounds))}"
          f"-bound", file=sys.stderr)
    return 100.0 * floor / kernel_s
