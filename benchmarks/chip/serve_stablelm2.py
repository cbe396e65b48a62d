"""Serving cells of StableLM-2: the published block at full width through
``ContinuousServingEngine``.

Only the model's hooks differ from :mod:`benchmarks.chip.serve`: its
sizes and weights (:mod:`benchmarks.chip.weights_stablelm2`), the
program's configuration (:func:`program_config`), and the plain reference
(:mod:`benchmarks.chip.reference_stablelm2`).  The closed loop, the book
of what each request did, the end-to-end numbers, the plan check and the
check's sample of finished requests are ``serve.py``'s, imported.  Mixes
are closed loops: every slot busy, each finished request replaced at once.
"""

from __future__ import annotations

import contextlib
import gc
import sys
import time
from pathlib import Path

import numpy as np

from . import reference, reference_stablelm2, traffic
from .harness import BenchError, BookError, CompileCounter, Tracer, annotate
from .serve import Book, percentile, sample_finished, serve_closed
from .weights_stablelm2 import Dims, make_params


def program_config(doc: dict):
    """The program's ``ModelConfig`` from the published config's keys."""
    from repro.models.config import ModelConfig

    if doc["use_parallel_residual"] or doc["qk_layernorm"]:
        raise BenchError(f"{doc['name']}: the program serves StableLM-2's "
                         f"sequential block without q/k LayerNorm")
    d = Dims.from_doc(doc)
    return ModelConfig(
        name=doc["name"], family="dense", n_layers=d.layers,
        d_model=d.d_model, n_heads=d.heads, n_kv_heads=d.kv_heads,
        head_dim=d.head_dim, d_ff=d.d_ff, vocab_size=d.vocab,
        rope_theta=d.rope_theta,
        rotary_fraction=float(doc["partial_rotary_factor"]),
        qkv_bias=bool(doc["use_qkv_bias"]), tie_embeddings=d.tied,
        norm="layer", norm_eps=d.norm_eps, dtype=doc["serve_dtype"])


def build_engine(doc: dict, params, mix: dict, root: Path):
    """The engine as ``launch/serve.py --continuous --width b --qos-budget
    B`` builds it, checked to serve the tables the configuration file
    states."""
    from repro.launch.serve import library_frontier, startup_plan
    from repro.library.qos import stack_luts
    from repro.precision.plans import select_width
    from repro.serving import ContinuousServingEngine, Telemetry

    cfg = program_config(doc)
    width = select_width(cfg, requested=int(doc["approx_bits"]))
    cfg = cfg.with_approx_mlp(bits=width.bits)
    with contextlib.redirect_stdout(sys.stderr):
        compiled, exact_area, _ = library_frontier(
            str(root / doc["library"]), width)
        plan = startup_plan(cfg, compiled, exact_area,
                            float(doc["qos_budget"]))
    if not np.array_equal(stack_luts(plan, compiled),
                          reference.served_tables(doc)):
        raise BookError("the program's plan does not serve the tables that "
                        "the configuration file states")
    engine = ContinuousServingEngine(
        cfg, params, max_slots=int(mix["slots"]),
        prompt_len=int(mix["prompt_tokens"][1]), gen_len=int(mix["gen_len"]),
        plan=plan, compiled=compiled, exact_area=exact_area)
    engine.start(telemetry=Telemetry())
    return engine


def run(cell, seed: int, seconds: float, tracing: bool, t_process: float,
        root: Path, scratch: Path, control: bool = False) -> dict:
    import jax

    doc, mix = cell.config, cell.traffic
    if mix["loop"] != "closed":
        raise BenchError(f"{cell.name}: this runner serves closed loops")
    program_config(doc)     # a program without the block stops here
    dims = Dims.from_doc(doc)
    slots, gen_len = int(mix["slots"]), int(mix["gen_len"])
    params = make_params(dims, seed)
    jax.block_until_ready(params)
    engine = build_engine(doc, params, mix, root)
    book = Book(dims, gen_len)
    stream = traffic.PromptStream(mix, dims.vocab, seed)
    serve_closed(engine, book, stream, slots, steps=int(mix["lead_in_steps"]))
    # what set-up made is never garbage: keep the collector off it
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_process

    first_window_step = len(book.steps)
    counter = CompileCounter()
    with Tracer(scratch if tracing else None) as tr:
        with counter:
            t_open = time.perf_counter()
            with annotate("window"):
                serve_closed(engine, book, stream, slots,
                             until=t_open + seconds)
        win = book.steps[first_window_step:]
        t_close = win[-1].end
    print(f"window: {len(win)} steps, {t_close - t_open:.3f} s; inside "
          f"it {counter}", file=sys.stderr)
    ends = [t_open] + [s.end for s in win]
    longest = sorted(np.diff(ends))[::-1][:3]
    print("longest step intervals in the window: "
          + ", ".join(f"{1e3 * x:.1f} ms" for x in longest), file=sys.stderr)
    mem = jax.devices()[0].memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))

    gaps = [b - a for r in book.reqs.values()
            for a, b in zip(r.token_t, r.token_t[1:]) if t_open < b <= t_close]
    metrics = {"setup_s": setup_s,
               "gap_p90_ms": 1e3 * percentile(gaps, 90) if gaps else None,
               "tok_s": sum(s.rows for s in win) / (t_close - t_open)}
    print(f"gaps: {len(gaps)} in the window, p50 "
          f"{1e3 * percentile(gaps, 50) if gaps else float('nan'):.1f} ms",
          file=sys.stderr)
    attempted = len(
        {r.rid for r in book.reqs.values() if r.admit_step is not None
         and r.admit_step < first_window_step + len(win)
         and (r.done_step is None or r.done_step >= first_window_step)})
    layer_ctx = None
    if tracing:
        layer_ctx = {"window_steps": win, "dims": dims,
                     "trace": tr.reduced(), "queue_waits": []}

    # the check: the program's state is freed before the reference runs
    finished = sorted((r for r in book.reqs.values()
                       if r.done_step is not None),
                      key=lambda r: (-len(r.prompt), r.rid))
    done_tokens = {r.rid: np.asarray(engine.completions[r.rid])
                   for r in finished}
    del engine, params
    gc.unfreeze()
    gc.collect()
    check = check_served(doc, dims, seed, finished, done_tokens,
                         int(mix["check_requests"]), control)
    out = {"metrics": metrics, "attempted": attempted, "failed": 0,
           "peak": peak, "check": check, "layer_ctx": layer_ctx}
    if control:
        out["check"], out["control_check"] = check
    return out


def check_served(doc, dims, seed, finished, done_tokens, n,
                 control: bool = False):
    """The check of the served tokens, as ``serve.check_served`` makes it,
    against this block's reference, with a second number beside the
    widest gap: the mean gap over every served token.

    A wrong block or a coarser precision moves the logits by more, so it
    flips more tokens and each by a wider gap: the mean grows about with
    the square of the logits' error where the widest gap grows with the
    error itself.  At this block's full-scale MLP the program's W8 codes
    flip with bf16 rounding, so the widest gap alone leaves the float8
    control less than twice the program's reading; the mean keeps them
    apart."""
    sample = sample_finished(finished, n, seed)
    limits = doc["limits"]
    missing = {"unchecked_requests": {"value": n - len(sample), "limit": 0}}
    if not sample:
        return (missing, missing) if control else missing
    got = reference_stablelm2.served_gaps(
        dims, seed, reference.layer_tiles(doc), [r.prompt for r in sample],
        [done_tokens[r.rid] for r in sample], control=control)
    checks = []
    for who, gaps in zip(("program", "control"),
                         got if control else (got,)):
        print(f"check ({who}): {len(sample)} requests, {gaps.size} served "
              f"tokens, widest gap {gaps.max():.6g}, mean gap "
              f"{gaps.mean():.6g}, {(gaps > 0).mean():.3f} of them "
              f"not the reference's first", file=sys.stderr)
        checks.append({
            **missing,
            "served_logit_gap": {
                "value": float(gaps.max()),
                "limit": float(limits["served_logit_gap"])},
            "served_logit_gap_mean": {
                "value": float(gaps.mean()),
                "limit": float(limits["served_logit_gap_mean"])}})
    distinct = np.unique(np.concatenate(
        [done_tokens[r.rid] for r in sample])).size
    print(f"check: {distinct} distinct served tokens", file=sys.stderr)
    return tuple(checks) if control else checks[0]
