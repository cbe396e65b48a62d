"""MLP weights prepared once: codes at one byte, per-column scales and
column sums, made by :func:`repro.quant.int4.prepare_weight` and read by
the LUT kernel as stored.

Everything here is bit for bit: ``approx_linear`` on a prepared weight
against the per-call form and against the int32-code arithmetic the
prepared form replaces, on both kernel backends; and the continuous
engine, which prepares its plan's MLP matrices when it is built, against
the same engine quantizing each weight on every call — tokens and logits,
across plan swaps that must neither retrace the step nor rebuild the
codes.
"""

from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.approx_matmul import takes_hi_pass  # noqa: E402
from repro.models import init_model  # noqa: E402
from repro.models.lm import prepare_mlp_weights  # noqa: E402
from repro.obs import trace as obs_trace  # noqa: E402
from repro.precision import compose  # noqa: E402
from repro.precision.plans import (build_mixed_ladder,  # noqa: E402
                                   load_frontier, load_mixed_frontier)
from repro.precision.widths import exact_table, width_from_lut  # noqa: E402
from repro.quant import (PreparedWeight, approx_linear,  # noqa: E402
                         prepare_weight, quantize_intb)
from repro.serving import (ContinuousServingEngine, PlanLadder,  # noqa: E402
                           Telemetry, make_profile)

SMOKE_LIB = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
             / "data" / "smoke-lib")


def _table(bits: int, kind: str, rng) -> np.ndarray:
    """The exact product table at ``bits``, or an approximate one whose
    (4-bit) tile reaches 375, so the kernel takes its ``hi`` pass."""
    if kind == "exact":
        return exact_table("mul", bits).astype(np.int32)
    block = rng.integers(0, 16, size=(4, 4))
    block[3, 3] = 15
    tile = compose.compose_table(block, "mul", 2, 4)
    lut = tile if bits == 4 else compose.tile_to_width(tile)
    assert takes_hi_pass(lut)
    return lut.astype(np.int32)


def _int32_form(x, w, lut, backend):
    """``approx_linear``'s arithmetic with the weight quantized here to
    int32 codes, as every call did before weights were prepared."""
    spec = width_from_lut(lut)
    bits, c = spec.bits, float(spec.bias)
    xq, sx = quantize_intb(x, bits, axis=-1)
    wq, sw = quantize_intb(w, bits, axis=0)
    raw = ops.approx_matmul(xq, wq, lut, backend=backend).astype(jnp.float32)
    sum_a = xq.sum(axis=1, keepdims=True).astype(jnp.float32)
    sum_b = wq.sum(axis=0, keepdims=True).astype(jnp.float32)
    out = (raw - c * sum_a - c * sum_b + c * c * x.shape[-1]) * sx * sw
    return out.astype(x.dtype)


@pytest.mark.parametrize("backend", ["ref", "pallas_interpret"])
@pytest.mark.parametrize("K", [128, 200], ids=["k128", "k200-padded"])
@pytest.mark.parametrize("kind", ["exact", "hi"])
@pytest.mark.parametrize("bits", [4, 8], ids=["w4", "w8"])
def test_prepared_weight_matches_per_call(bits, kind, K, backend, rng):
    """The kernel reads the one-byte codes (uint8) as it reads int32
    ones: prepared and per call agree bit for bit with the int32 form on
    the gather oracle."""
    M, N = 8, 136
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(K, N)) / np.sqrt(K), jnp.bfloat16)
    lut = jnp.asarray(_table(bits, kind, rng))
    prepared = jax.jit(prepare_weight, static_argnums=1)(w, bits)
    assert prepared.codes.dtype == jnp.uint8
    assert prepared.codes.shape == (K, N)
    assert prepared.scale.shape == prepared.colsum.shape == (1, N)

    run = jax.jit(lambda x, w, lut: approx_linear(x, w, lut,
                                                  backend=backend))
    got = np.asarray(run(x, prepared, lut).astype(jnp.float32))
    per_call = np.asarray(run(x, w, lut).astype(jnp.float32))
    int32 = np.asarray(jax.jit(
        lambda x, w, lut: _int32_form(x, w, lut, "ref"))(x, w, lut)
        .astype(jnp.float32))
    assert np.array_equal(got, per_call)
    assert np.array_equal(got, int32)


def test_prepared_stack_equals_each_matrix_prepared_alone(rng):
    w = jnp.asarray(rng.normal(size=(3, 96, 40)), jnp.bfloat16)
    stack = prepare_weight(w, 8)
    for i in range(3):
        one = prepare_weight(w[i], 8)
        for a, b in zip(jax.tree.leaves(one),
                        jax.tree.leaves(jax.tree.map(lambda a: a[i],
                                                     stack))):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    wq, sw = quantize_intb(w[1], 8, axis=0)
    assert np.array_equal(np.asarray(stack.codes[1]), np.asarray(wq))
    assert np.array_equal(np.asarray(stack.colsum[1]),
                          np.asarray(wq.sum(axis=0, keepdims=True)))
    assert np.array_equal(np.asarray(stack.scale[1]),
                          np.asarray(sw, np.float32))


def test_prepared_weight_refuses_another_width(rng):
    w = jnp.asarray(rng.normal(size=(32, 16)), jnp.bfloat16)
    x = jnp.asarray(rng.normal(size=(2, 32)), jnp.bfloat16)
    with pytest.raises(ValueError, match="prepared at 8 bits"):
        approx_linear(x, prepare_weight(w, 8),
                      jnp.asarray(exact_table("mul", 4), jnp.int32))


# --------------------------------------------------------------------------
# the continuous engine: codes made once, tokens and logits unchanged
# --------------------------------------------------------------------------

def _ladder(lib, cfg, widths):
    """A plan ladder over ``lib`` at ``widths`` (a width, or a per-layer
    width map), with its top level approximate."""
    if isinstance(widths, int):
        compiled, exact_area, _ = load_frontier(lib, widths)
        ladder = PlanLadder.build(compiled, cfg.n_layers,
                                  exact_area=exact_area, levels=3)
        return ladder, dict(compiled=compiled, exact_area=exact_area)
    mixed = load_mixed_frontier(lib)
    sens = {b: np.ones(cfg.n_layers) for b in mixed.widths}
    ladder = build_mixed_ladder(mixed, widths, sens, levels=3)
    return ladder, dict(compiled=mixed.compiled, sensitivities=sens,
                        width_map=widths)


class NoCosts(Telemetry):
    """Telemetry without the cost plane: a composed W8 operator's
    glue-inclusive upper area exceeds the exact table's, and the cost
    counters refuse the negative saving that gives (an open defect of the
    cost plane, not of what this file tests)."""

    def record_costs(self, *args, **kw):
        pass


def _serve(cfg, params, ladder, kw, *, per_call: bool):
    """Serve a fixed load on the ladder's top plan, swap down twice and
    serve again; returns the engine, every step's logits and the codes'
    leaves before the swaps."""
    top = len(ladder) - 1
    eng = ContinuousServingEngine(
        cfg, params, max_slots=2, prompt_len=6, gen_len=4, page_size=4,
        plan=ladder.plan(top), **kw)
    if per_call:
        # what the step did before: each MLP matmul quantizes its weight
        eng._prepared = None
    logits = []
    step = eng._jit_step
    eng._jit_step = lambda *a: (out := step(*a), logits.append(
        np.asarray(out[0])))[0]
    prof = make_profile("steady", ticks=2, per_tick=2, prompt_len=6,
                        gen_len=4, prompt_dist=("bimodal", 3, 6))
    codes = jax.tree.leaves(eng._prepared)
    eng.serve(prof, telemetry=NoCosts(), seed=0)
    for level in (top - 1, 0):
        eng.swap_plan(ladder.plan(level), ladder.luts(level))
    eng.serve(prof, telemetry=NoCosts(), seed=1)
    return eng, logits, codes


@pytest.mark.parametrize("arch,widths", [
    pytest.param("qwen3-4b", 4, id="qwen3-w4"),
    pytest.param("stablelm-1.6b", 8, id="stablelm-w8"),
    pytest.param("qwen3-4b", (4, 8), id="qwen3-mixed-4-8")])
def test_engine_serves_prepared_codes_as_per_call(arch, widths, tmp_path):
    bits = widths if isinstance(widths, int) else max(widths)
    cfg = get_config(arch, reduced=True).with_approx_mlp(bits=bits)
    params = init_model(cfg, jax.random.PRNGKey(0))
    ladder, kw = _ladder(SMOKE_LIB, cfg, widths)
    assert ladder.plan(len(ladder) - 1).plan_id != ladder.plan(0).plan_id

    obs_trace.configure(tmp_path / "trace", process_tag="prepared")
    try:
        eng, logits, codes = _serve(cfg, params, ladder, kw, per_call=False)
    finally:
        obs_trace.reset(clear_env=True)
    ref, ref_logits, _ = _serve(cfg, params, ladder, kw, per_call=True)

    assert len(logits) == len(ref_logits) > 0
    for got, want in zip(logits, ref_logits):
        assert np.array_equal(got, want)
    assert set(eng.completions) == set(ref.completions)
    for rid, toks in eng.completions.items():
        assert np.array_equal(toks, ref.completions[rid]), rid
    # made once: the swaps reused the one trace and the same arrays
    assert eng.trace_count == 1
    after = jax.tree.leaves(eng._prepared)
    assert len(after) == len(codes) == 9 * (
        1 if isinstance(widths, int) else len(set(widths)))
    assert all(a is b for a, b in zip(codes, after))

    n_mats = 3 * cfg.n_layers
    nbytes = sum(a.nbytes for a in after)
    assert nbytes >= n_mats * cfg.d_model * cfg.d_ff   # a byte a code
    (plan_event,) = [s["attrs"] for s in obs_trace.read_trace(tmp_path / "trace")
                     if s["name"] == "serve.plan"]
    assert plan_event["weight_codes"] == n_mats
    assert plan_event["weight_code_bytes"] == nbytes


def test_mixed_groups_follow_the_width_map():
    cfg = get_config("qwen3-4b", reduced=True).with_approx_mlp(bits=8)
    params = init_model(cfg, jax.random.PRNGKey(0))
    groups = prepare_mlp_weights(params, (8, 4))
    assert sorted(groups) == [4, 8]
    w1 = params["layers"]["ffn"]["w1"]
    for bits, layer in ((8, 0), (4, 1)):
        got = groups[bits]["w1"]
        assert isinstance(got, PreparedWeight) and got.bits == bits
        want = prepare_weight(w1[layer], bits)
        assert np.array_equal(np.asarray(got.codes[0]),
                              np.asarray(want.codes))
