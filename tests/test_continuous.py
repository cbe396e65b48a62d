"""Continuous batching: paged KV allocator, slot pool, weighted-fair
admission, SLO preemption, and the multi-replica router.

The contract under test everywhere: requests join/leave/preempt/resume
per decode step while the jitted step traces exactly once, and the page
allocator's conservation invariants hold at every boundary.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.arith import benchmark  # noqa: E402
from repro.library.compile import load_mul_frontier  # noqa: E402
from repro.models import (decode_fn, decode_paged_fn, init_caches,  # noqa: E402
                          init_model, init_paged_caches)
from repro.sensitivity.classes import ClassBook, ClassScheduler  # noqa: E402
from repro.serving import (ContinuousServingEngine, ControllerConfig,  # noqa: E402
                           OutOfPages, PageAllocator, PlanLadder,
                           QoSController, Replica, ReplicaRouter, SeqState,
                           SlotPool, Telemetry, WeightedFairQueues,
                           effective_load_ms, make_profile,
                           parse_prompt_dist)
from repro.serving.kvcache import SCRATCH_PAGE  # noqa: E402
from repro.serving.loadgen import synth_requests  # noqa: E402

from test_serving import fill_library, trunc_mul2, zero_mul2  # noqa: E402


# --------------------------------------------------------------------------
# page allocator
# --------------------------------------------------------------------------

def test_allocator_conservation_and_reuse():
    a = PageAllocator(n_pages=6, page_size=4)
    t1 = a.alloc(1, 10)          # 3 pages
    t2 = a.alloc(2, 5)           # 2 pages
    a.check_invariants()
    assert len(t1) == 3 and len(t2) == 2
    assert a.used_pages == 5 and a.free_pages == 1
    assert SCRATCH_PAGE not in t1 + t2
    assert a.free(1) == 3
    a.check_invariants()
    # LIFO reuse: the same admission sequence replays the same tables
    t3 = a.alloc(3, 10)
    assert t3 == t1
    a.check_invariants()


def test_allocator_double_alloc_and_foreign_free():
    a = PageAllocator(n_pages=4, page_size=4)
    a.alloc(7, 4)
    with pytest.raises(ValueError, match="already holds"):
        a.alloc(7, 4)
    with pytest.raises(ValueError, match="holds no pages"):
        a.free(8)
    a.check_invariants()


def test_allocator_out_of_pages_is_clean():
    a = PageAllocator(n_pages=2, page_size=4)
    a.alloc(1, 8)
    assert not a.can_alloc(1)
    with pytest.raises(OutOfPages):
        a.alloc(2, 1)
    # the failed alloc must not leak or corrupt anything
    a.check_invariants()
    assert a.free_pages == 0 and not a.holds(2)
    a.free(1)
    assert a.can_alloc(8)


def test_padded_table_scratch_fill():
    a = PageAllocator(n_pages=4, page_size=4)
    a.alloc(1, 6)   # 2 pages
    row = a.padded_table(1, 4)
    assert row.dtype == np.int32 and row.shape == (4,)
    assert tuple(row[:2]) == a.table(1)
    assert all(p == SCRATCH_PAGE for p in row[2:])
    empty = a.padded_table(None, 4)
    assert all(p == SCRATCH_PAGE for p in empty)


# --------------------------------------------------------------------------
# SLO class spec / drain weights
# --------------------------------------------------------------------------

def test_class_spec_slo_parse():
    book = ClassBook.parse("gold:0.02@8ms, std:0.05, batch:0.2@1500ms")
    assert book.get("gold").slo_ms == 8.0
    assert book.get("std").slo_ms is None
    assert book.get("batch").slo_ms == 1500.0
    assert [c.name for c in book] == ["gold", "std", "batch"]


def test_class_spec_slo_rejects_nonpositive():
    with pytest.raises(ValueError):
        ClassBook.parse("gold:0.02@0ms")
    with pytest.raises(ValueError):
        ClassBook.parse("gold:0.02@-5ms")


def test_drain_weights_priority_order():
    book = ClassBook.parse("gold:0.02,std:0.05,batch:0.2")
    w = book.drain_weights()
    assert w == {"gold": 4, "std": 2, "batch": 1}


# --------------------------------------------------------------------------
# prompt-length distributions
# --------------------------------------------------------------------------

def test_prompt_dist_parse():
    assert parse_prompt_dist("uniform:4-16", 16) == ("uniform", 4, 16)
    assert parse_prompt_dist("bimodal:2-8", 8) == ("bimodal", 2, 8)
    for bad in ("gauss:4-16", "uniform:0-16", "uniform:9-8",
                "uniform:4-17", "uniform"):
        with pytest.raises(ValueError):
            parse_prompt_dist(bad, 16)


def test_prompt_dist_deterministic_and_bounded():
    prof = make_profile("steady", ticks=3, per_tick=5, prompt_len=16,
                        gen_len=4, prompt_dist=("bimodal", 3, 16))
    a = synth_requests(prof, 128, seed=9)
    b = synth_requests(prof, 128, seed=9)
    lens = []
    for ta, tb in zip(a, b):
        for ra, rb in zip(ta, tb):
            assert np.array_equal(ra.tokens, rb.tokens)
            assert 3 <= len(ra.tokens) <= 16
            lens.append(len(ra.tokens))
    assert len(set(lens)) > 1, "bimodal draw produced uniform lengths"


def test_prompt_dist_tokens_are_fixed_length_prefix():
    """Length variation must not reshuffle content: each request's tokens
    are a prefix of the same request's fixed-length draw."""
    kw = dict(ticks=2, per_tick=4, prompt_len=12, gen_len=4)
    fixed = synth_requests(make_profile("steady", **kw), 128, seed=3)
    mixed = synth_requests(
        make_profile("steady", prompt_dist=("uniform", 2, 12), **kw),
        128, seed=3)
    for tf, tm in zip(fixed, mixed):
        for rf, rm in zip(tf, tm):
            assert np.array_equal(rm.tokens, rf.tokens[: len(rm.tokens)])


# --------------------------------------------------------------------------
# slot pool / weighted-fair queues / controller signal
# --------------------------------------------------------------------------

def _seq(rid, cls="std", prompt_len=4, gen_len=4):
    return SeqState(rid=rid, cls=cls,
                    prompt=np.arange(prompt_len, dtype=np.int32),
                    gen_len=gen_len, submitted_t=0.0)


def test_seqstate_decode_math():
    s = _seq(0, prompt_len=3, gen_len=2)
    outs = []
    fed = []
    while not s.done:
        fed.append(s.next_token())
        outs.append(s.advance(100 + s.pos))
    # prompt positions 0..1 are prefill; the step fed position 2 (the
    # last prompt token) produces the first generated token, so the whole
    # request takes prompt + gen - 1 = 4 steps
    assert outs == [(False, False), (False, False), (True, True),
                    (True, False)]
    assert fed == [0, 1, 2, 102]   # last fed token is generated[0]
    assert len(s.generated) == 2
    assert s.n_tokens == 5


def test_pick_victim_worst_class_then_youngest():
    pool = SlotPool(4)
    prio = {"gold": 0, "std": 1, "batch": 2}
    pool.place(0, _seq(11, "batch"))
    pool.place(1, _seq(5, "std"))
    pool.place(2, _seq(12, "batch"))
    pool.place(3, _seq(2, "gold"))
    # gold arrival (prio 0): worst tier wins, youngest rid breaks the tie
    assert pool.pick_victim(lambda c: prio[c], below=0) == 2
    pool.evict(2)
    assert pool.pick_victim(lambda c: prio[c], below=0) == 0
    pool.evict(0)
    assert pool.pick_victim(lambda c: prio[c], below=0) == 1
    # nothing strictly below std remains for a std arrival
    pool.evict(1)
    assert pool.pick_victim(lambda c: prio[c], below=1) is None


def test_weighted_fair_shares():
    q = WeightedFairQueues(("gold", "batch"), {"gold": 2, "batch": 1})
    for i in range(30):
        q.push("gold", f"g{i}")
        q.push("batch", f"b{i}")
    picks = [q.pick()[0] for _ in range(30)]
    assert picks.count("gold") == 20 and picks.count("batch") == 10
    # deterministic schedule: replay is bit-identical
    q2 = WeightedFairQueues(("gold", "batch"), {"gold": 2, "batch": 1})
    for i in range(30):
        q2.push("gold", f"g{i}")
        q2.push("batch", f"b{i}")
    assert [q2.pick()[0] for _ in range(30)] == picks


def test_weighted_fair_admissible_filter_and_resume_front():
    q = WeightedFairQueues(("gold", "batch"))
    q.push("gold", 1)
    q.push("batch", 2)
    # gold's head inadmissible (e.g. out of pages) -> batch is served,
    # gold stays queued rather than being dropped
    cls, item = q.pick(admissible=lambda it: it != 1)
    assert (cls, item) == ("batch", 2)
    assert q.peek("gold") == 1 and len(q) == 1
    # resume path: a preempted item re-enters at the head of its class
    q.push("gold", 3)
    q.push_front("gold", 99)
    assert q.pick()[1] == 99


def test_effective_load_uses_occupancy_and_queue():
    raw = 10.0
    # fixed-batch form: backlog against capacity
    assert effective_load_ms(raw, backlog=0, capacity=4) == raw
    assert effective_load_ms(raw, backlog=4, capacity=4) == 2 * raw
    # continuous form: slot occupancy replaces the implicit full batch
    assert effective_load_ms(raw, backlog=0, capacity=4,
                             occupancy=0.5) == 0.5 * raw
    assert effective_load_ms(raw, backlog=2, capacity=4,
                             occupancy=1.0) == 1.5 * raw


# --------------------------------------------------------------------------
# paged decode vs dense decode (exact numerics)
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lm():
    cfg = get_config("gemma3-1b", reduced=True)
    params = init_model(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def approx_setup(tmp_path_factory):
    root = tmp_path_factory.mktemp("contlib")
    store = fill_library(root / "lib", [benchmark("mul_i4"), trunc_mul2(),
                                        zero_mul2()])
    compiled, exact_area, _ = load_mul_frontier(root / "lib")
    cfg = get_config("gemma3-1b", reduced=True).with_approx_mlp()
    params = init_model(cfg, jax.random.PRNGKey(0))
    ladder = PlanLadder.build(compiled, cfg.n_layers, exact_area=exact_area,
                              levels=4)
    return root, store, compiled, exact_area, cfg, params, ladder


def test_paged_decode_matches_dense(lm):
    """Two requests staggered into a 3-slot pool, paged KV, vs each
    decoded alone in a dense cache — logits must match exactly."""
    cfg, params = lm
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in (12, 7)]
    joins = [0, 3]
    total, page_size, slots = 16, 4, 3
    n_pages = slots * (total // page_size) + 1

    dense_step = decode_fn(cfg)
    refs = []
    for p in prompts:
        caches = init_caches(cfg, 1, total)
        out = []
        for t in range(total - 1):
            tok = jnp.asarray([[p[t] if t < len(p) else out[-1]]],
                              dtype=jnp.int32)
            logits, caches = dense_step(cfg, params, caches, tok,
                                        jnp.asarray(t, jnp.int32))
            out.append(int(jnp.argmax(logits[0])))
        refs.append(out)

    pstep = decode_paged_fn(cfg)
    caches = init_paged_caches(cfg, slots, n_pages, page_size, total)
    alloc = PageAllocator(n_pages, page_size)
    tables = {i: alloc.alloc(i, total) for i in range(len(prompts))}
    pos = [0, 0]
    outs = [[], []]
    for step in range(total - 1 + max(joins)):
        toks = np.zeros((slots, 1), np.int32)
        posv = np.zeros(slots, np.int32)
        act = np.zeros(slots, bool)
        tab = np.full((slots, total // page_size), SCRATCH_PAGE, np.int32)
        for i, p in enumerate(prompts):
            if step < joins[i] or pos[i] >= total - 1:
                continue
            t = pos[i]
            toks[i, 0] = p[t] if t < len(p) else outs[i][-1]
            posv[i] = t
            act[i] = True
            tab[i] = tables[i]
        if not act.any():
            break
        logits, caches = pstep(cfg, params, caches, jnp.asarray(toks),
                               jnp.asarray(posv), jnp.asarray(act),
                               jnp.asarray(tab))
        samp = np.asarray(jnp.argmax(logits, axis=-1))
        for i in range(len(prompts)):
            if act[i]:
                outs[i].append(int(samp[i]))
                pos[i] += 1
    for i, ref in enumerate(refs):
        assert outs[i] == ref, f"request {i} diverged from dense decode"


# --------------------------------------------------------------------------
# continuous engine end to end
# --------------------------------------------------------------------------

def _profile(kind="ramp", ticks=4, per_tick=4, prompt_len=8, gen_len=8,
             class_mix=None, prompt_dist=("bimodal", 3, 8)):
    return make_profile(kind, ticks=ticks, per_tick=per_tick,
                        prompt_len=prompt_len, gen_len=gen_len,
                        class_mix=class_mix, prompt_dist=prompt_dist)


def _run_plain(cfg, params, compiled, exact_area, ladder, *, max_slots=2,
               n_pages=None, seed=0, profile=None):
    eng = ContinuousServingEngine(
        cfg, params, max_slots=max_slots, prompt_len=8, gen_len=8,
        page_size=4, n_pages=n_pages, plan=ladder.plan(0),
        compiled=compiled, exact_area=exact_area)
    tel = eng.serve(profile or _profile(), telemetry=Telemetry(), seed=seed)
    return eng, tel


def test_continuous_completes_all_trace_pinned(approx_setup):
    _, _, compiled, exact_area, cfg, params, ladder = approx_setup
    prof = _profile()
    eng, tel = _run_plain(cfg, params, compiled, exact_area, ladder,
                          profile=prof)
    assert eng.trace_count == 1, "join/leave churn retraced the step"
    assert len(eng.completions) == prof.total_requests
    assert all(len(g) == prof.gen_len for g in eng.completions.values())
    # drained pool returned every page
    eng._alloc.check_invariants()
    assert eng._alloc.used_pages == 0
    s = tel.summary()
    assert s["requests"] == prof.total_requests
    assert s["steps"] > prof.gen_len, "no continuous per-step accounting"

    # determinism: same seed, same completions
    eng2, _ = _run_plain(cfg, params, compiled, exact_area, ladder,
                         profile=prof)
    assert set(eng2.completions) == set(eng.completions)
    for rid, gen in eng.completions.items():
        assert np.array_equal(gen, eng2.completions[rid]), rid


STEP_PHASES = ["serve.step.admit", "serve.step.inputs", "serve.step.launch",
               "serve.step.wait", "serve.step.sample", "serve.step.book"]


def test_step_once_spans_each_phase_in_order(tmp_path, approx_setup):
    """Every engine step is one ``serve.step`` span holding its six phase
    spans, in order and nested in time; tracing serves the same tokens
    and the step still traces once."""
    from repro.obs import trace as obs_trace
    from repro.obs.trace import read_trace

    _, _, compiled, exact_area, cfg, params, ladder = approx_setup
    prof = _profile()
    plain, _ = _run_plain(cfg, params, compiled, exact_area, ladder,
                          profile=prof)
    obs_trace.configure(tmp_path, process_tag="serve")
    try:
        eng, tel = _run_plain(cfg, params, compiled, exact_area, ladder,
                              profile=prof)
    finally:
        obs_trace.reset()
    assert eng.trace_count == 1
    assert set(eng.completions) == set(plain.completions)
    for rid, gen in plain.completions.items():
        assert np.array_equal(gen, eng.completions[rid]), rid

    spans = read_trace(tmp_path)
    kids: dict[str, list] = {}
    for s in spans:
        kids.setdefault(s["parent"], []).append(s)
    steps = [s for s in spans if s["name"] == "serve.step"
             and s["attrs"]["rows"] > 0]
    assert len(steps) == tel.summary()["steps"]
    assert [s["attrs"]["step"] for s in steps] == list(range(len(steps)))
    for s in steps:
        phases = sorted((k for k in kids[s["id"]]
                         if k["name"].startswith("serve.step.")),
                        key=lambda k: k["t0"])
        assert [k["name"] for k in phases] == STEP_PHASES
        assert 1 <= s["attrs"]["rows"] <= 2
        assert 0 <= s["attrs"]["prefill_rows"] <= s["attrs"]["rows"]
        end, ulp = s["t0"], 1e-6     # t0 + dur_s rounds in float seconds
        for k in phases:
            assert k["t0"] >= end - ulp
            assert k["t0"] + k["dur_s"] <= s["t0"] + s["dur_s"] + ulp
            end = k["t0"] + k["dur_s"]
    # a call with nothing to run is a step span with its admission alone
    for s in spans:
        if s["name"] == "serve.step" and s["attrs"]["rows"] == 0:
            assert [k["name"] for k in kids.get(s["id"], [])
                    if k["name"].startswith("serve.step.")] == \
                ["serve.step.admit"]


def test_continuous_under_serving_mesh_traces_once(approx_setup):
    """The serve CLI runs the engine under its mesh: the page pools must
    start on that mesh, or the step's first outputs differ in type from
    its first inputs and the step traces twice."""
    from repro import parallel
    from repro.launch.mesh import make_smoke_mesh

    _, _, compiled, exact_area, cfg, params, ladder = approx_setup
    mesh = make_smoke_mesh()
    with parallel.activate(mesh), mesh:
        eng, _ = _run_plain(cfg, params, compiled, exact_area, ladder,
                            profile=_profile(ticks=2))
    assert eng.trace_count == 1


def test_lowered_step_reuses_the_served_trace(approx_setup):
    _, _, compiled, exact_area, cfg, params, ladder = approx_setup
    eng, _ = _run_plain(cfg, params, compiled, exact_area, ladder,
                        profile=_profile(ticks=1))
    text = eng.lowered_step().as_text()
    assert eng.trace_count == 1
    # the per-layer LUT stack is an argument of the lowered step
    assert f"tensor<{cfg.n_layers}x16x16xi32>" in text


def test_out_of_pages_blocks_admission_never_corrupts(approx_setup):
    _, _, compiled, exact_area, cfg, params, ladder = approx_setup
    # pool holds exactly one in-flight request's pages (4 of them) plus
    # one spare page: the second arrival MUST wait in queue, not corrupt
    prof = _profile(kind="steady", ticks=2, per_tick=3)
    eng = ContinuousServingEngine(
        cfg, params, max_slots=2, prompt_len=8, gen_len=8, page_size=4,
        n_pages=5, plan=ladder.plan(0), compiled=compiled,
        exact_area=exact_area)
    saw_block = []

    def on_step(e, step):
        e._alloc.check_invariants()
        if e.queue_depth > 0 and e._pool.n_active < e.max_slots:
            saw_block.append(step)   # a free slot existed but pages didn't

    tel = eng.serve(prof, telemetry=Telemetry(), seed=0, on_step_end=on_step)
    assert saw_block, "pool was never page-limited; test is vacuous"
    assert len(eng.completions) == prof.total_requests
    assert all(len(g) == prof.gen_len for g in eng.completions.values())
    assert eng._alloc.used_pages == 0
    assert eng.trace_count == 1


def _slo_stack(ladder, spec="gold:1e9@250ms,batch:1e9"):
    book = ClassBook.parse(spec)
    scheduler = ClassScheduler(book, ladder, shadow_every=4)
    controller = QoSController(ladder, ControllerConfig(
        target_ms_per_step=50.0, drift_budget=1e9, shadow_every=4))
    return book, scheduler, controller


def _preemption_run(cfg, params, compiled, exact_area, ladder, health=None):
    _, scheduler, controller = _slo_stack(ladder)
    prof = _profile(kind="spike", ticks=6, per_tick=5, gen_len=12,
                    class_mix=(("gold", 0.4), ("batch", 0.6)),
                    prompt_dist=("uniform", 3, 8))
    eng = ContinuousServingEngine(
        cfg, params, max_slots=2, prompt_len=8, gen_len=12, page_size=4,
        plan=ladder.plan(0), compiled=compiled, exact_area=exact_area)
    tel = eng.serve(prof, controller=controller, scheduler=scheduler,
                    telemetry=Telemetry(), seed=1, steps_per_tick=5,
                    health=health)
    preempted = [(e["step"], e["preempted_rid"]) for e in tel.events
                 if "preempted_rid" in e]
    return eng, tel, prof, preempted


def test_slo_preemption_fires_and_is_deterministic(approx_setup):
    _, _, compiled, exact_area, cfg, params, ladder = approx_setup
    eng, tel, prof, preempted = _preemption_run(cfg, params, compiled,
                                                exact_area, ladder)
    assert preempted, "SLO class never preempted a batch slot"
    assert eng.trace_count == 1, "preemption/resume retraced the step"
    assert len(eng.completions) == prof.total_requests
    assert eng._alloc.used_pages == 0
    s = tel.summary()
    assert s["preemptions"] == len(preempted)
    # preemptions are charged to the victim tier, never to gold
    assert "preemptions" not in s["classes"].get("gold", {})
    # gold's latency stayed inside its (generous, CPU-scale) SLO
    assert s["classes"]["gold"]["p95_ms_per_step"] <= 250.0
    # TTFT per class was recorded as a histogram
    assert s["classes"]["gold"]["p95_ttft_ms"] > 0
    assert s["ttft_ms"]["p95"] >= s["ttft_ms"]["p50"] > 0

    _, _, _, preempted2 = _preemption_run(cfg, params, compiled,
                                          exact_area, ladder)
    assert preempted2 == preempted, "preemption schedule is not deterministic"


def test_preempted_request_resumes_uncorrupted(approx_setup):
    """A preempted+resumed request must produce the same tokens as when
    the pool is large enough that it is never preempted.  Class budgets
    pin every level to exact so the LUT stack cannot differ."""
    _, _, compiled, exact_area, cfg, params, ladder = approx_setup
    prof = _profile(kind="spike", ticks=6, per_tick=5, gen_len=12,
                    class_mix=(("gold", 0.4), ("batch", 0.6)),
                    prompt_dist=("uniform", 3, 8))

    def run(max_slots):
        _, scheduler, _ = _slo_stack(ladder, "gold:1e-12@250ms,batch:1e-12")
        eng = ContinuousServingEngine(
            cfg, params, max_slots=max_slots, prompt_len=8, gen_len=12,
            page_size=4, plan=ladder.plan(0), compiled=compiled,
            exact_area=exact_area)
        tel = eng.serve(prof, scheduler=scheduler, telemetry=Telemetry(),
                        seed=1, steps_per_tick=5)
        return eng, tel

    tight, tel_tight = run(2)
    roomy, _ = run(8)
    assert tel_tight.preemptions >= 1, "tight pool never preempted"
    assert roomy.preemption_count == 0, "roomy pool should never preempt"
    assert set(tight.completions) == set(roomy.completions)
    for rid in tight.completions:
        assert np.array_equal(tight.completions[rid],
                              roomy.completions[rid]), (
            f"request {rid} corrupted by preemption/resume")


def test_request_lifecycle_and_provenance_e2e(tmp_path, approx_setup):
    """The tentpole e2e: a traced preemption run reconstructs a complete
    causal chain (queued -> admitted -> prefill -> decode -> preempt ->
    resume -> done) for EVERY request, with a breakdown that sums to the
    total, a gap-free provenance ledger, and both CLI gates passing — all
    while the decode step still traces exactly once."""
    from repro.obs import trace as obs_trace
    from repro.obs.__main__ import main as obs_main
    from repro.obs.provenance import _ledgers, audit, read_ledger
    from repro.obs.requests import BREAKDOWN_KEYS, build_timelines
    from repro.obs.trace import read_trace

    _, _, compiled, exact_area, cfg, params, ladder = approx_setup
    trace_dir = tmp_path / "trace"
    obs_trace.configure(trace_dir, process_tag="serve")
    try:
        eng, tel, prof, preempted = _preemption_run(cfg, params, compiled,
                                                    exact_area, ladder)
    finally:
        obs_trace.reset()
        _ledgers.clear()
    assert preempted, "run never preempted; lifecycle e2e is vacuous"
    assert eng.trace_count == 1, "lifecycle tracing retraced the step"

    tls = build_timelines(read_trace(trace_dir))
    assert len(tls) == prof.total_requests
    broken = {t.rid: t.problems for t in tls.values() if not t.complete}
    assert not broken, f"broken lifecycle chains: {broken}"
    resumed = [t for t in tls.values() if t.preempts > 0]
    assert resumed, "no preempted-and-resumed request completed a chain"
    for t in tls.values():
        assert set(t.breakdown) == set(BREAKDOWN_KEYS)
        assert t.steps is not None and t.steps >= prof.gen_len
        assert t.total_ms is not None and t.total_ms > 0
    assert any(t.breakdown["suspension_ms"] > 0 for t in resumed), \
        "resumed requests recorded no suspension time"

    # ledger: every completed request's ranges tile [0, gen_len) and the
    # drift samples the engine measured were attributed to ranges
    rep = audit(read_ledger(trace_dir))
    assert rep["n_done"] == prof.total_requests
    assert rep["n_failed"] == 0
    assert rep["n_complete"] == prof.total_requests
    assert all(r["tokens_covered"] == prof.gen_len
               for r in rep["requests"].values())
    assert sum(r["drift_samples"] for r in rep["requests"].values()) > 0
    # resumed requests still tile their ledger (the victim pick prefers
    # the youngest slot, so preemption usually lands mid-prefill and the
    # decode window stays one contiguous range — a mid-decode preempt
    # would seal and split, which the unit audit tests pin down)
    for t in resumed:
        assert rep["requests"][t.rid]["complete"], rep["requests"][t.rid]

    # both CI gates pass against the real artifacts
    assert obs_main(["requests", "--trace", str(trace_dir),
                     "--require-complete"]) == 0
    assert obs_main(["provenance", "--trace", str(trace_dir)]) == 0

    # per-class queueing-delay and suspension histograms rode telemetry
    reg = tel.registry
    assert reg.find("serve_queue_delay_ms", **{"class": "gold"}).count > 0
    assert reg.find("serve_suspension_ms", **{"class": "_all"}).count \
        == len(preempted)


def test_resume_mirrors_into_health_event_log(approx_setup):
    """Satellite of the lifecycle work: every resume is a *control*
    event — it lands in the health plane's attribution log (paired with
    the preempt that caused it), so an anomaly right after a resume
    pins to the resume instead of a stale earlier swap."""
    class _StubHealth:
        def __init__(self):
            self.noted = []

        def observe_step(self, **kw):
            return {"state": "ok"}

        def note_event(self, name, **kw):
            self.noted.append((name, kw))

        def record_crash(self, e):
            pass

    _, _, compiled, exact_area, cfg, params, ladder = approx_setup
    hp = _StubHealth()
    _, _, _, preempted = _preemption_run(cfg, params, compiled, exact_area,
                                         ladder, health=hp)
    assert preempted
    resumes = [kw for name, kw in hp.noted if name == "serve.resume"]
    assert len(resumes) == len(preempted), \
        "every preempted request that came back must note serve.resume"
    assert all("rid" in kw and "cls" in kw and "step" in kw
               for kw in resumes)
    preempt_rids = sorted(rid for _, rid in preempted)
    assert sorted(kw["rid"] for kw in resumes) == preempt_rids


def test_prov_range_seals_on_plan_change_and_preempt(tmp_path):
    """The engine's range bookkeeping, driven directly: contiguous same-
    plan tokens extend one range; a plan change or a preemption seals it;
    the resumed tail still tiles [0, gen_len) for the audit."""
    from repro.obs.provenance import ProvenanceLedger, audit, read_ledger

    class _Plan:
        def __init__(self, pid):
            self.plan_id, self.choices = pid, []

    eng = ContinuousServingEngine.__new__(ContinuousServingEngine)
    eng._provenance = ProvenanceLedger(tmp_path, tag="w")
    eng._prov_open = {}
    eng._width_map = None
    seq = SeqState(rid=1, cls="gold", prompt=np.array([1, 2], np.int32),
                   gen_len=6, submitted_t=0.0)
    p0, p1 = _Plan("p0"), _Plan("p1")
    eng._prov_extend(seq, 0, p0, 0)
    eng._prov_extend(seq, 1, p0, 0)     # contiguous same-plan: extends
    eng._prov_extend(seq, 2, p1, 1)     # plan change: seals [0, 2)
    eng._prov_close(1)                  # preemption: seals [2, 3)
    eng._prov_extend(seq, 3, p1, 1)     # resume reopens
    eng._prov_extend(seq, 4, p1, 1)
    eng._prov_extend(seq, 5, p1, 1)
    eng._prov_close(1)
    eng._provenance.record_done(rid=1, cls="gold", gen_len=6, steps=7,
                                preempts=1)
    eng._provenance.close()

    rep = audit(read_ledger(tmp_path))
    req = rep["requests"][1]
    assert req["complete"], req["problems"]
    assert [(r["t0"], r["t1"], r["plan"], r["level"])
            for r in req["ranges"]] \
        == [(0, 2, "p0", 0), (2, 3, "p1", 1), (3, 6, "p1", 1)]


# --------------------------------------------------------------------------
# multi-replica router
# --------------------------------------------------------------------------

def test_router_affinity_and_per_replica_plans(approx_setup):
    from repro.library import OperatorSignature
    from repro.core.synth import area as circuit_area
    from repro.serving import LibraryWatcher

    root, store, compiled, exact_area, cfg, params, ladder = approx_setup

    def mk(level):
        return ContinuousServingEngine(
            cfg, params, max_slots=2, prompt_len=8, gen_len=8, page_size=4,
            plan=ladder.plan(level), compiled=compiled,
            exact_area=exact_area)

    with pytest.raises(ValueError, match="at least 2"):
        ReplicaRouter([Replica("solo", mk(0))])

    router = ReplicaRouter([
        Replica("gold-exact", mk(0), classes=("gold",)),
        Replica("batch-deep", mk(len(ladder) - 1), classes=("batch",)),
    ], watcher=LibraryWatcher(root / "lib", min_poll_s=0.0))
    prof = _profile(kind="ramp", ticks=4, per_tick=4,
                    class_mix=(("gold", 0.5), ("batch", 0.5)),
                    prompt_dist=("uniform", 3, 8))
    out = router.serve(prof, seed=0)

    assert out["requests"] == prof.total_requests
    assert sum(router.routed.values()) == prof.total_requests
    assert all(v > 0 for v in router.routed.values()), router.routed
    per = out["replicas"]
    assert all(r["trace_count"] == 1 for r in per.values())
    # per-replica plan state: exact-tile replica vs deep-level replica
    assert per["gold-exact"]["plan"] != per["batch-deep"]["plan"]
    for r in router.replicas:
        assert r.engine._alloc.used_pages == 0


def test_router_routes_by_class_affinity(approx_setup):
    from repro.serving.loadgen import Request

    _, _, compiled, exact_area, cfg, params, ladder = approx_setup

    def mk():
        return ContinuousServingEngine(
            cfg, params, max_slots=2, prompt_len=8, gen_len=8, page_size=4,
            plan=ladder.plan(0), compiled=compiled, exact_area=exact_area)

    router = ReplicaRouter([Replica("a", mk(), classes=("gold",)),
                            Replica("b", mk(), classes=("batch",))])
    router.start()
    tok = np.arange(4, dtype=np.int32)
    assert router.route(Request(0, tok, qos_class="gold")).name == "a"
    assert router.route(Request(1, tok, qos_class="batch")).name == "b"
    # unhomed class falls back to least-loaded (both idle -> first)
    assert router.route(Request(2, tok, qos_class="std")).name == "a"
