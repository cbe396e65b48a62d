"""Adaptive serving runtime: controller hysteresis, plan ladder/refresh
invariants, store watcher, and the end-to-end hot-swap serve (CPU,
reduced model) with a single decode trace."""

import numpy as np
import pytest

from repro.core.arith import benchmark
from repro.core.circuits import Circuit, Op
from repro.core.synth import area
from repro.library import (
    OperatorSignature,
    OperatorStore,
    plan_ladder,
    refresh_plan,
    select_plan,
    validate_lut_stack,
)
from repro.library.compile import load_mul_frontier
from repro.serving import (
    ControllerConfig,
    LibraryWatcher,
    PlanLadder,
    QoSController,
    Telemetry,
    steady,
)
from repro.serving.loadgen import make_profile, synth_requests


# ---------------------------------------------------------------------------
# handcrafted 2-bit multipliers: deterministic frontier rungs for the tests
# ---------------------------------------------------------------------------
def trunc_mul2() -> Circuit:
    """Exact low 2 product bits, upper bits dropped (wce 8, small area)."""
    c = Circuit.empty(4, "trunc_mul2")
    a0, a1, b0, b1 = 0, 1, 2, 3
    p0 = c.add(Op.AND, a0, b0)
    p1 = c.add(Op.XOR, c.add(Op.AND, a1, b0), c.add(Op.AND, a0, b1))
    z = c.const(False)
    for out in (p0, p1, z, z):
        c.mark_output(out)
    return c


def zero_mul2() -> Circuit:
    """Constant-zero multiplier (wce 9, ~zero area) — the frontier floor."""
    c = Circuit.empty(4, "zero_mul2")
    z = c.const(False)
    for _ in range(4):
        c.mark_output(z)
    return c


def ones_mul2() -> Circuit:
    """Every product reads 15: a 4-bit table composed from it reaches
    15 x 25 = 375, past what the LUT kernel's one pass takes."""
    c = Circuit.empty(4, "ones_mul2")
    one = c.const(True)
    for _ in range(4):
        c.mark_output(one)
    return c


def fill_library(root, circuits) -> OperatorStore:
    store = OperatorStore(root)
    exact_vals = benchmark("mul_i4").eval_words().astype(np.int64)
    for circ in circuits:
        wce = int(np.abs(circ.eval_words().astype(np.int64) - exact_vals).max())
        store.put_circuit(
            circ, OperatorSignature("mul", 2, "wce", max(1, wce)),
            area=area(circ), source="test",
        )
    return store


@pytest.fixture()
def two_op_library(tmp_path):
    """Exact + truncated multiplier: a 2-rung frontier."""
    root = tmp_path / "lib"
    fill_library(root, [benchmark("mul_i4"), trunc_mul2()])
    return root


# ---------------------------------------------------------------------------
# plan ladder / refresh / validation (library.qos extensions)
# ---------------------------------------------------------------------------
def test_plan_ladder_monotone(two_op_library):
    compiled, exact_area, _ = load_mul_frontier(two_op_library)
    sens = np.ones(3)
    ladder = plan_ladder(compiled, sens, exact_area=exact_area, levels=5)
    assert len(ladder) >= 2
    assert all(c.key is None for c in ladder[0].choices)  # level 0 = exact
    areas = [p.total_area for p in ladder]
    drifts = [p.predicted_total for p in ladder]
    assert all(a > b for a, b in zip(areas, areas[1:])), areas
    assert all(a <= b for a, b in zip(drifts, drifts[1:])), drifts
    # last level is the full descent: every layer on its cheapest rung
    cheapest = min(rec.area for rec, _ in compiled)
    assert all(c.area == cheapest for c in ladder[-1].choices)


def test_plan_ladder_minimum_levels_reach_full_descent(two_op_library):
    """Even the coarsest ladder must span exact -> full greedy descent,
    otherwise a post-refresh controller can never reach the cheap plans."""
    compiled, exact_area, _ = load_mul_frontier(two_op_library)
    cheapest = min(rec.area for rec, _ in compiled)
    for levels in (2, 3):
        ladder = plan_ladder(compiled, np.ones(2), exact_area=exact_area,
                             levels=levels)
        assert all(c.key is None for c in ladder[0].choices)
        assert all(c.area == cheapest for c in ladder[-1].choices), levels


def test_refresh_plan_keeps_budget_and_monotonicity(tmp_path):
    root = tmp_path / "lib"
    store = fill_library(root, [benchmark("mul_i4"), trunc_mul2()])
    compiled, exact_area, _ = load_mul_frontier(root)
    sens = np.ones(4)
    lo = select_plan(compiled, sens, 1.0, exact_area=exact_area)
    hi = select_plan(compiled, sens, 1e9, exact_area=exact_area)

    # densify the store, refresh both plans against the new frontier
    circ = zero_mul2()
    store.put_circuit(circ, OperatorSignature("mul", 2, "wce", 9),
                      area=area(circ), source="test")
    compiled2, exact_area2, _ = load_mul_frontier(root)
    assert len(compiled2) == len(compiled) + 1
    lo2 = refresh_plan(lo, compiled2, sens, exact_area=exact_area2)
    hi2 = refresh_plan(hi, compiled2, sens, exact_area=exact_area2)
    assert lo2.budget == lo.budget and hi2.budget == hi.budget
    # monotonicity survives the refresh: tighter budget never buys more area
    assert lo2.total_area >= hi2.total_area
    # the unbounded plan adopts the newly added cheaper operator everywhere
    assert hi2.total_area < hi.total_area


def test_validate_lut_stack_rejects_mismatch():
    ok = np.zeros((4, 16, 16), np.int32)
    validate_lut_stack(ok, np.ones((4, 16, 16), np.int32))  # no raise
    with pytest.raises(ValueError, match="refusing"):
        validate_lut_stack(ok, np.zeros((5, 16, 16), np.int32))
    with pytest.raises(ValueError, match="refusing"):
        validate_lut_stack(ok, np.zeros((4, 16, 16), np.int64))


def test_plan_id_tracks_assignment_not_budget(two_op_library):
    compiled, exact_area, _ = load_mul_frontier(two_op_library)
    sens = np.ones(2)
    a = select_plan(compiled, sens, 0.0, exact_area=exact_area)
    b = select_plan(compiled, sens, 1e-9, exact_area=exact_area)
    c = select_plan(compiled, sens, 1e9, exact_area=exact_area)
    assert a.plan_id == b.plan_id        # same assignment, different budget
    assert a.plan_id != c.plan_id


# ---------------------------------------------------------------------------
# controller hysteresis
# ---------------------------------------------------------------------------
def _ladder(library, n_layers=2, levels=4):
    compiled, exact_area, _ = load_mul_frontier(library)
    return PlanLadder.build(compiled, n_layers, exact_area=exact_area,
                            levels=levels)


def test_controller_no_flap_on_oscillating_latency(two_op_library):
    ladder = _ladder(two_op_library)
    ctrl = QoSController(ladder, ControllerConfig(
        target_ms_per_step=50.0, drift_budget=1.0, patience=2, cooldown=1,
        ewma_alpha=0.3))
    # oscillation straddling the band: streaks keep resetting -> no move
    for i in range(40):
        assert ctrl.observe(80.0 if i % 2 else 20.0) is None
    assert ctrl.moves == 0 and ctrl.level == 0
    # oscillation *inside* the deadband: no move either
    for i in range(40):
        assert ctrl.observe(53.0 if i % 2 else 47.0) is None
    assert ctrl.moves == 0


def test_controller_walks_up_under_load_then_down_on_drift(two_op_library):
    ladder = _ladder(two_op_library)
    top = len(ladder) - 1
    ctrl = QoSController(ladder, ControllerConfig(
        target_ms_per_step=10.0, drift_budget=0.1, patience=1, cooldown=0,
        ewma_alpha=1.0))
    # sustained overload with drift headroom: walk up to the cheapest level
    levels = [ctrl.observe(100.0, drift=0.0) for _ in range(top + 2)]
    assert ctrl.level == top
    assert [l for l in levels if l is not None] == list(range(1, top + 1))
    # drift headroom gone: walks back down even though still overloaded
    ctrl.observe(100.0, drift=10.0)
    assert ctrl.level == top - 1
    assert ctrl.last_reason == "drift"


def test_controller_idle_steps_back_toward_exact(two_op_library):
    ladder = _ladder(two_op_library)
    ctrl = QoSController(ladder, ControllerConfig(
        target_ms_per_step=50.0, drift_budget=1.0, patience=2, cooldown=0,
        ewma_alpha=1.0), level=len(ladder) - 1)
    for _ in range(2):
        ctrl.observe(10.0)
    assert ctrl.level == len(ladder) - 2
    assert ctrl.last_reason == "idle"


def test_controller_cooldown_spaces_moves(two_op_library):
    ladder = _ladder(two_op_library, levels=6)
    if len(ladder) < 3:
        pytest.skip("frontier too coarse for a 3-level ladder")
    ctrl = QoSController(ladder, ControllerConfig(
        target_ms_per_step=10.0, drift_budget=1.0, patience=1, cooldown=3,
        ewma_alpha=1.0))
    moves = [ctrl.observe(100.0) for _ in range(8)]
    moved_at = [i for i, m in enumerate(moves) if m is not None]
    assert all(b - a >= 4 for a, b in zip(moved_at, moved_at[1:])), moved_at


def test_controller_refresh_clamps_level(two_op_library, tmp_path):
    ladder = _ladder(two_op_library)
    ctrl = QoSController(ladder, ControllerConfig(), level=len(ladder) - 1)
    compiled, exact_area, _ = load_mul_frontier(two_op_library)
    ctrl.refresh(compiled[:1], exact_area)   # frontier collapsed to 1 op
    assert ctrl.level <= len(ctrl.ladder) - 1


def test_ladder_refresh_keeps_requested_resolution(tmp_path):
    """A sparse frontier dedups the ladder; refreshing against a denser
    one must regain the *requested* level count, not ratchet down."""
    root = tmp_path / "lib"
    store = fill_library(root, [benchmark("mul_i4"), trunc_mul2()])
    compiled, exact_area, _ = load_mul_frontier(root)
    sparse = PlanLadder.build(compiled[:1], 4, exact_area=exact_area,
                              levels=6)
    assert sparse.requested_levels == 6
    circ = zero_mul2()
    store.put_circuit(circ, OperatorSignature("mul", 2, "wce", 9),
                      area=area(circ), source="test")
    compiled2, exact_area2, _ = load_mul_frontier(root)
    dense = sparse.refresh(compiled2, exact_area2)
    assert len(dense) > len(sparse)
    assert dense.requested_levels == 6


# ---------------------------------------------------------------------------
# watcher / store version token
# ---------------------------------------------------------------------------
def test_version_token_changes_on_put(tmp_path):
    store = fill_library(tmp_path / "lib", [benchmark("mul_i4")])
    t0 = store.version_token()
    assert t0 == store.version_token()       # stable across reads
    circ = trunc_mul2()
    store.put_circuit(circ, OperatorSignature("mul", 2, "wce", 8),
                      area=area(circ), source="test")
    assert store.version_token() != t0


def test_watcher_detects_midrun_put(two_op_library):
    watcher = LibraryWatcher(two_op_library, min_poll_s=0.0)
    assert not watcher.poll()                # nothing changed yet
    store = OperatorStore(two_op_library)
    circ = zero_mul2()
    store.put_circuit(circ, OperatorSignature("mul", 2, "wce", 9),
                      area=area(circ), source="fleet")
    assert watcher.poll()                    # change seen exactly once
    assert not watcher.poll()
    compiled, _, bits = watcher.load_frontier()
    assert bits == 2
    assert any(r.wce == 9 for r, _ in compiled)


def test_watcher_rate_limit(two_op_library):
    now = [0.0]
    watcher = LibraryWatcher(two_op_library, min_poll_s=5.0,
                             clock=lambda: now[0])
    store = OperatorStore(two_op_library)
    circ = zero_mul2()
    store.put_circuit(circ, OperatorSignature("mul", 2, "wce", 9),
                      area=area(circ), source="fleet")
    now[0] = 1.0
    assert not watcher.poll()                # inside the rate limit
    now[0] = 6.0
    assert watcher.poll()


# ---------------------------------------------------------------------------
# loadgen / telemetry
# ---------------------------------------------------------------------------
def test_loadgen_profiles_deterministic():
    p = make_profile("ramp", ticks=5, per_tick=4, prompt_len=8, gen_len=2)
    assert p.arrivals[-1] == 4 and p.n_ticks == 5
    r1 = synth_requests(p, vocab_size=128, seed=3)
    r2 = synth_requests(p, vocab_size=128, seed=3)
    flat1 = [t for tick in r1 for r in tick for t in r.tokens.tolist()]
    flat2 = [t for tick in r2 for r in tick for t in r.tokens.tolist()]
    assert flat1 == flat2
    assert sum(len(t) for t in r1) == p.total_requests
    spike_p = make_profile("spike", ticks=8, per_tick=6)
    assert max(spike_p.arrivals) == 6 and min(spike_p.arrivals) == 1


def test_telemetry_ring_bounds_and_summary(two_op_library):
    compiled, exact_area, _ = load_mul_frontier(two_op_library)
    plan = select_plan(compiled, np.ones(2), 1e9, exact_area=exact_area)
    tel = Telemetry(capacity=4)
    tel.register_plan(plan)
    for b in range(10):
        tel.record_batch(batch=b, tick=b, n_requests=2, prefill_s=0.1,
                         decode_s=0.2, prefill_tokens=8, decode_tokens=16,
                         decode_steps=8, plan_id=plan.plan_id)
    tel.record_swap(batch=9, reason="qos-load", old=None, new=plan.plan_id)
    assert len(tel.events) == 4              # ring stays bounded
    s = tel.summary()
    assert s["batches"] == 10 and s["requests"] == 20
    assert s["swaps"] == 1 and s["swaps_by_reason"] == {"qos-load": 1}
    assert s["decode_tok_s"] == pytest.approx(16 / 0.2, rel=1e-3)
    assert s["prefill_tok_s"] == pytest.approx(8 / 0.1, rel=1e-3)


# ---------------------------------------------------------------------------
# end-to-end: adaptive serve with controller + watcher hot-swaps, one trace
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["fixed", "continuous"])
def test_e2e_adaptive_serve_hot_swaps_without_retrace(tmp_path, kind):
    """Both engines' own control plane: the watcher's library refresh and
    the controller's level moves swap plans under one decode trace."""
    jax = pytest.importorskip("jax")
    from repro.configs import get_config
    from repro.models import init_model
    from repro.serving import ContinuousServingEngine, ServingEngine

    lib = tmp_path / "lib"
    store = fill_library(lib, [benchmark("mul_i4"), trunc_mul2()])
    compiled, exact_area, _ = load_mul_frontier(lib)

    cfg = get_config("gemma3-1b", reduced=True).with_approx_mlp()
    params = init_model(cfg, jax.random.PRNGKey(0))

    ladder = PlanLadder.build(compiled, cfg.n_layers, exact_area=exact_area,
                              levels=4)
    assert len(ladder) >= 2
    # unreachable latency target -> sustained "overload" on any machine, so
    # the controller must walk the frontier up; huge drift budget keeps the
    # walk unobstructed
    ctrl = QoSController(ladder, ControllerConfig(
        target_ms_per_step=1e-6, drift_budget=1e9, patience=1, cooldown=0,
        shadow_every=1, ewma_alpha=1.0))
    watcher = LibraryWatcher(lib, min_poll_s=0.0)

    def densify_midrun(engine, idx):
        if idx == 2:   # a "background fleet sweep" lands a cheaper op
            circ = zero_mul2()
            store.put_circuit(circ, OperatorSignature("mul", 2, "wce", 9),
                              area=area(circ), source="fleet")

    plan_kw = dict(plan=ladder.plan(0), compiled=compiled,
                   exact_area=exact_area)
    profile = steady(6, 2, prompt_len=4, gen_len=4)
    if kind == "fixed":
        engine = ServingEngine(cfg, params, batch=2, prompt_len=4,
                               gen_len=4, **plan_kw)
        tel = engine.serve(profile, controller=ctrl, watcher=watcher,
                           telemetry=Telemetry(),
                           on_batch_end=densify_midrun)
    else:
        engine = ContinuousServingEngine(cfg, params, max_slots=2,
                                         prompt_len=4, gen_len=4,
                                         page_size=4, **plan_kw)
        tel = engine.serve(profile, controller=ctrl, watcher=watcher,
                           telemetry=Telemetry(),
                           on_step_end=densify_midrun)

    reasons = {s["reason"] for s in tel.swaps}
    assert any(r.startswith("qos-") for r in reasons), tel.swaps
    assert "library" in reasons, tel.swaps
    assert tel.swap_count >= 2
    # the decode step was traced exactly once across every swap
    assert engine.trace_count == 1
    # drift was sampled against the exact shadow step
    assert any(e["drift"] is not None for e in tel.events)
    # the serve ended on a cheaper-than-exact plan that includes the
    # mid-run operator (zero_mul2 has area ~0)
    assert engine.plan.total_area < ladder.plan(0).total_area
    keys_used = {c.key for c in engine.plan.choices}
    new_keys = {r.key for r, _ in engine._compiled if r.wce == 9}
    assert keys_used & new_keys, (keys_used, new_keys)
    if kind == "continuous":
        return
    s = tel.summary()
    assert s["batches"] == 6 and s["requests"] == 12
    assert s["plans_used"] >= 2


def test_plan_events_report_wide_lut_layers(tmp_path):
    """The engine's first plan (exact tables) needs no second kernel pass;
    a swap to a plan of the composed all-15 operator reports its layers."""
    jax = pytest.importorskip("jax")
    from repro.configs import get_config
    from repro.models import init_model
    from repro.obs import trace as obs_trace
    from repro.serving import ServingEngine

    lib = tmp_path / "lib"
    fill_library(lib, [benchmark("mul_i4"), ones_mul2()])
    compiled, exact_area, _ = load_mul_frontier(lib)
    cfg = get_config("gemma3-1b", reduced=True).with_approx_mlp()
    params = init_model(cfg, jax.random.PRNGKey(0))
    ladder = PlanLadder.build(compiled, cfg.n_layers, exact_area=exact_area,
                              levels=4)
    top = len(ladder) - 1
    ones = {r.key for r, _ in compiled if r.wce == 15}
    routed = sum(c.key in ones for c in ladder.plan(top).choices)
    assert routed > 0
    obs_trace.configure(tmp_path / "trace", process_tag="plan")
    try:
        engine = ServingEngine(cfg, params, batch=2, prompt_len=4,
                               gen_len=4, plan=ladder.plan(0),
                               compiled=compiled, exact_area=exact_area)
        assert engine.swap_plan(ladder.plan(top), ladder.luts(top))
    finally:
        obs_trace.reset(clear_env=True)
    events = {s["name"]: s["attrs"]
              for s in obs_trace.read_trace(tmp_path / "trace")}
    assert events["serve.plan"]["wide_lut_layers"] == 0
    assert events["serve.swap"]["wide_lut_layers"] == routed
    assert events["serve.plan"]["lut_bits"] == 4
    assert events["serve.swap"]["lut_bits"] == 4


def test_e2e_plain_engine_single_trace(tmp_path):
    jax = pytest.importorskip("jax")
    from repro.configs import get_config
    from repro.models import init_model
    from repro.serving import ServingEngine

    cfg = get_config("gemma3-1b", reduced=True)
    params = init_model(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, batch=2, prompt_len=4, gen_len=4)
    tel = engine.serve(steady(2, 3, prompt_len=4, gen_len=4))
    # 3 arrivals/tick on batch=2 -> two batches per tick (one short, padded)
    assert tel.n_batches == 4 and tel.n_requests == 6
    assert engine.trace_count == 1
    assert tel.summary()["decode_tok_s"] > 0
    # the short final batch keeps only the real request's completion
    assert engine.last_tokens.shape == (1, 4)
