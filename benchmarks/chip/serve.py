"""Serving cells: a full-width model through ``ContinuousServingEngine``.

The harness drives the engine's own entry points, ``submit`` and
``step_once``, from one thread.  A closed loop keeps every slot busy and
replaces each finished request at once; an open loop submits requests
at the due times of ``traffic.ArrivalClock`` (in seconds), each with its
due time, so a stall shows in the latency of every request behind it.

Per-request times come from the harness's clock.  The engine admits in
submission order and advances every admitted request one position per
step, so from the queue depth after each step the harness knows which
step admitted a request, which step gave each of its tokens, and which
step finished it; every finish is checked against ``engine.completions``.

After the window the program's state is freed and the plain reference
(:mod:`benchmarks.chip.reference`) runs over a sample of the finished
requests, drawn from the seed with the longest among them.  The number
compared is the widest gap by which a served token's logit lies below
the reference's best at that position.
"""

from __future__ import annotations

import contextlib
import gc
import sys
import time
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from . import reference, traffic, work
from .harness import BookError, CompileCounter, Tracer, annotate
from .weights import Dims, make_params


@dataclass
class Req:
    rid: int
    prompt: np.ndarray
    due: float
    admit_step: int | None = None
    token_t: list = field(default_factory=list)   # step end of each token
    done_step: int | None = None

    @property
    def first_t(self) -> float | None:
        return self.token_t[0] if self.token_t else None


@dataclass
class Step:
    end: float
    rows: int
    flops: int


class Book:
    """What every request did, from the harness's side of the engine."""

    def __init__(self, dims: Dims, gen_len: int) -> None:
        self.dims = dims
        self.gen_len = gen_len
        self.reqs: dict[int, Req] = {}
        self.queued: deque[Req] = deque()
        self.active: dict[int, Req] = {}
        self.steps: list[Step] = []
        self.late: list[float] = []   # how late each submit ran (s)

    @property
    def in_system(self) -> int:
        return len(self.queued) + len(self.active)

    def submit(self, engine, prompt: traffic.Prompt, due: float) -> None:
        from repro.serving.loadgen import Request

        self.late.append(time.perf_counter() - due)
        r = Req(prompt.rid, prompt.tokens, due)
        self.reqs[r.rid] = r
        self.queued.append(r)
        engine.submit(Request(rid=r.rid, tokens=prompt.tokens), now=due)

    def step(self, engine) -> bool:
        t0 = time.perf_counter()
        ran = engine.step_once(t0)
        t1 = time.perf_counter()
        if not ran:
            return False
        idx = len(self.steps)
        for _ in range(len(self.queued) - engine.queue_depth):
            r = self.queued.popleft()
            r.admit_step = idx
            self.active[r.rid] = r
        flops = 0
        for r in list(self.active.values()):
            pos = idx - r.admit_step
            flops += work.flops_per_token(self.dims, pos)
            if pos >= len(r.prompt) - 1:
                r.token_t.append(t1)
            if len(r.token_t) == self.gen_len:
                r.done_step = idx
                del self.active[r.rid]
                got = engine.completions.get(r.rid)
                if got is None or len(got) != self.gen_len:
                    raise BookError(f"request {r.rid} should have finished "
                                    f"at step {idx}")
        if len(engine.completions) != sum(
                r.done_step is not None for r in self.reqs.values()):
            raise BookError(f"the engine finished requests the harness did "
                            f"not expect at step {idx}")
        self.steps.append(Step(t1, len(self.active) + sum(
            r.done_step == idx for r in self.reqs.values()), flops))
        return True


def program_config(doc: dict):
    from repro.models.config import ModelConfig

    return ModelConfig(
        name=doc["name"], family="dense",
        n_layers=int(doc["num_hidden_layers"]),
        d_model=int(doc["hidden_size"]),
        n_heads=int(doc["num_attention_heads"]),
        n_kv_heads=int(doc["num_key_value_heads"]),
        head_dim=int(doc["head_dim"]), d_ff=int(doc["intermediate_size"]),
        vocab_size=int(doc["vocab_size"]), qk_norm=bool(doc["qk_norm"]),
        rope_theta=float(doc["rope_theta"]),
        tie_embeddings=bool(doc["tie_word_embeddings"]),
        norm_eps=float(doc["rms_norm_eps"]), dtype=doc["torch_dtype"])


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

    class Waits(Telemetry):
        """The engine's own queue waits, in admission order."""

        def __init__(self) -> None:
            super().__init__()
            self.waits: list[float] = []

        def record_queue(self, qos_class, depth, wait_s=()):
            self.waits.extend(float(w) for w in wait_s)
            super().record_queue(qos_class, depth, wait_s)

    engine = ContinuousServingEngine(
        cfg, params, max_slots=int(mix["slots"]),
        prompt_len=int(mix["prompt_tokens"][1]), gen_len=int(mix["gen_len"]),
        plan=plan, compiled=compiled, exact_area=exact_area)
    tel = Waits()
    engine.start(telemetry=tel)
    return engine, tel


def serve_closed(engine, book: Book, stream, slots: int, *, steps=None,
                 until=None) -> None:
    """Keep ``slots`` requests in the system; run ``steps`` steps, or
    every step that starts before ``until``."""
    n = 0
    while (steps is not None and n < steps) or (
            until is not None and time.perf_counter() < until):
        with annotate("submit"):
            while book.in_system < slots:
                book.submit(engine, stream.next(), time.perf_counter())
        with annotate("step_once"):
            book.step(engine)
        n += 1


def serve_open(engine, book: Book, stream, clock, origin: float, *,
               until: float | None = None, stop=None) -> None:
    """Submit at the clock's due times and step while there is work, until
    ``until`` passes or ``stop()`` holds."""
    while True:
        now = time.perf_counter()
        if (until is not None and now >= until) or (stop is not None
                                                    and stop()):
            return
        with annotate("submit"):
            while origin + clock.t <= now:
                book.submit(engine, stream.next(), origin + clock.t)
                clock.next()
        if book.in_system:
            with annotate("step_once"):
                book.step(engine)
        else:
            time.sleep(max(0.0, min(origin + clock.t - now, 0.01)))


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))


def run(cell, seed: int, seconds: float, tracing: bool, t_process: float,
        root: Path, scratch: Path, control: bool = False) -> dict:
    import jax

    doc, mix = cell.config, cell.traffic
    dims = Dims.from_doc(doc)
    slots, gen_len = int(mix["slots"]), int(mix["gen_len"])
    params = make_params(dims, seed)
    jax.block_until_ready(params)
    engine, tel = build_engine(doc, params, mix, root)
    book = Book(dims, gen_len)
    stream = traffic.PromptStream(mix, dims.vocab, seed)
    closed = mix["loop"] == "closed"
    clock = origin = None
    if closed:
        serve_closed(engine, book, stream, slots,
                     steps=int(mix["lead_in_steps"]))
    else:
        clock = traffic.ArrivalClock(float(mix["rate_per_s"]), seed)
        clock.next()
        # one request and one step first: the step compiles here in a fresh
        # checkout, before the clock starts, so no backlog of arrivals
        # built up meanwhile reaches the window
        book.submit(engine, stream.next(), time.perf_counter())
        book.step(engine)
        origin = time.perf_counter()
        serve_open(engine, book, stream, clock, origin,
                   until=origin + float(mix["lead_in_s"]))
    # what set-up made is never garbage: keep the collector off it
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_process

    first_window_step = len(book.steps)
    counter = CompileCounter()
    due_in: list[Req] = []
    # the profiler stays on through the drain: stopping it takes seconds,
    # which would otherwise delay the requests still open at the close
    with Tracer(scratch if tracing else None) as tr:
        with counter:
            t_open = time.perf_counter()
            t_end = t_open + seconds
            with annotate("window"):
                if closed:
                    serve_closed(engine, book, stream, slots, until=t_end)
                else:
                    serve_open(engine, book, stream, clock, origin,
                               until=t_end)
        win = book.steps[first_window_step:]
        t_close = win[-1].end

        def due_in_window() -> list[Req]:
            return [r for r in book.reqs.values() if t_open <= r.due < t_end]

        if not closed:
            # every request due in the window counts, also one whose due
            # time fell inside the window's last step, submitted after it
            cap = t_close + float(mix["drain_cap_s"])
            serve_open(engine, book, stream, clock, origin, until=cap,
                       stop=lambda: origin + clock.t >= t_end and all(
                           r.first_t is not None for r in due_in_window()))
            due_in = due_in_window()
    print(f"window: {len(win)} steps, {t_close - t_open:.3f} s; inside "
          f"it {counter}", file=sys.stderr)
    ends = [t_open] + [s.end for s in win]
    longest = sorted(np.diff(ends))[::-1][:3]
    print("longest step intervals in the window: "
          + ", ".join(f"{1e3 * x:.1f} ms" for x in longest), file=sys.stderr)
    mem = jax.devices()[0].memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))

    # end-to-end numbers, all from the window
    gaps = [b - a for r in book.reqs.values()
            for a, b in zip(r.token_t, r.token_t[1:]) if t_open < b <= t_close]
    metrics = {"setup_s": setup_s,
               "gap_p90_ms": 1e3 * percentile(gaps, 90) if gaps else None,
               "tok_s": sum(s.rows for s in win) / (t_close - t_open)}
    if due_in:
        end = time.perf_counter()
        ttft = [(r.first_t if r.first_t is not None else end) - r.due
                for r in due_in]
        metrics["ttft_p90_ms"] = 1e3 * percentile(ttft, 90)
        print(f"ttft over {len(ttft)} requests due in the window: p50 "
              f"{1e3 * percentile(ttft, 50):.1f} ms, p90 "
              f"{metrics['ttft_p90_ms']:.1f} ms", file=sys.stderr)
    print(f"gaps: {len(gaps)} in the window, p50 "
          f"{1e3 * percentile(gaps, 50) if gaps else float('nan'):.1f} ms; "
          f"submit lateness p90 {1e3 * percentile(book.late, 90):.3f} ms",
          file=sys.stderr)
    attempted = len(due_in) if not closed else len(
        {r.rid for r in book.reqs.values() if r.admit_step is not None
         and r.admit_step < first_window_step + len(win)
         and (r.done_step is None or r.done_step >= first_window_step)})
    failed = sum(r.first_t is None for r in due_in)

    layer_ctx = None
    if tracing:
        waits = {rid: w for rid, w in zip(
            sorted(r.rid for r in book.reqs.values()
                   if r.admit_step is not None), tel.waits)}
        layer_ctx = {"window_steps": win, "dims": dims,
                     "trace": tr.reduced(),
                     "queue_waits": [waits[r.rid] for r in due_in
                                     if r.rid in waits]}

    # the check: the program's state is freed before the reference runs
    finished = sorted((r for r in book.reqs.values()
                       if r.done_step is not None),
                      key=lambda r: (-len(r.prompt), r.rid))
    done_tokens = {r.rid: np.asarray(engine.completions[r.rid])
                   for r in finished}
    del engine, params, tel
    gc.unfreeze()
    gc.collect()
    check = check_served(doc, dims, seed, finished, done_tokens,
                         int(mix["check_requests"]), control)
    out = {"metrics": metrics, "attempted": attempted, "failed": failed,
           "peak": peak, "check": check, "layer_ctx": layer_ctx}
    if control:
        out["check"], out["control_check"] = check
    return out


def sample_finished(finished: list[Req], n: int, seed: int) -> list[Req]:
    """The longest finished request and ``n - 1`` others drawn from the
    seed."""
    if not finished:
        return []
    rest = finished[1:]
    pick = traffic.rng_for(seed, 7).permutation(len(rest))[:n - 1]
    return [finished[0]] + [rest[i] for i in sorted(pick)]


def check_served(doc, dims, seed, finished, done_tokens, n,
                 control: bool = False):
    """The check of the served tokens: ``{name: {value, limit}}``.  With
    ``control``, a pair: the program's check and the same check of the
    tokens that the float8 control puts first at the same positions."""
    sample = sample_finished(finished, n, seed)
    limit = float(doc["limits"]["served_logit_gap"])
    missing = {"unchecked_requests": {"value": n - len(sample), "limit": 0}}
    if not sample:
        return (missing, missing) if control else missing
    got = reference.served_gaps(
        dims, seed, reference.layer_tiles(doc), [r.prompt for r in sample],
        [done_tokens[r.rid] for r in sample], control=control)
    checks = []
    for who, gaps in zip(("program", "control"),
                         got if control else (got,)):
        print(f"check ({who}): {len(sample)} requests, {gaps.size} served "
              f"tokens, widest gap {gaps.max():.6g}, median "
              f"{np.median(gaps):.6g}, {(gaps > 0).mean():.3f} of them "
              f"not the reference's first", file=sys.stderr)
        checks.append({**missing, "served_logit_gap": {
            "value": float(gaps.max()), "limit": limit}})
    distinct = np.unique(np.concatenate(
        [done_tokens[r.rid] for r in sample])).size
    print(f"check: {distinct} distinct served tokens", file=sys.stderr)
    return tuple(checks) if control else checks[0]
