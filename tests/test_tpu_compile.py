"""The main-path Pallas kernels compile for a TPU v5e chip at real widths.

Interpret mode accepts kernels that the chip's compiler (Mosaic) refuses,
so each kernel is compiled here for a *described* chip — one of a
``v5e:2x2`` topology, no device attached — at the shapes it runs at:
the LUT matmul at the Qwen3-4B decode MLP shapes (8, 64 and 128 rows)
and at StableLM-2-1.6B's on its 8-bit path, with int32 codes and with the
one-byte (uint8) codes the serving engines prepare, flash attention at one
32-head 512-token block, and ``template_eval`` at a paper-scale
population, alone and split over the four chips the fleet search shards
it across.  A kernel that reached the chip appears in the compiled
program as a ``tpu_custom_call``.  One paged decode attention layer is
compiled at each batch cell's shapes, to hold the contraction to the bf16
gather of K and V.  The continuous engine's step, lowered for the chip at
reduced shapes, holds no reduction or conversion of an MLP weight: its
weights are prepared once, outside the step.
"""

import functools
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.core.arith import benchmark
from repro.core.circuits import input_truth_tables
from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.approx_matmul import approx_matmul_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.template_eval import template_eval_pallas
from repro.models.config import ModelConfig
from repro.models import init_model
from repro.models.layers import attention_decode_paged
from repro.precision.plans import load_frontier
from repro.serving import (ContinuousServingEngine, PlanLadder, Request,
                           Telemetry)

D_MODEL, D_FF, DECODE_M = 2560, 9728, 8   # Qwen3-4B MLP, decode rows
SERVED_M = (64, 128)      # the rows of the served chat and batch steps


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return fn.lower(*args).compile().as_text()


def _assert_lut_matmul_compiles(one_chip, M, K, N, side, b_dtype):
    """The LUT matmul compiles for the chip, and reads a one-byte ``b``
    as it is passed, with no int32 copy of it."""
    a, lut = (jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
              for shape in ((M, K), (side, side)))
    b = jax.ShapeDtypeStruct((K, N), b_dtype, sharding=one_chip)
    text = _compiled_text(approx_matmul_pallas, a, b, lut)
    assert "tpu_custom_call" in text
    if b_dtype == jnp.uint8:
        assert f"u8[{K},{N}]" in text and f"s32[{K},{N}]" not in text


@pytest.mark.parametrize("side", [16, 256], ids=["w4", "w8"])
@pytest.mark.parametrize("M,K,N,b_dtype", [
    pytest.param(m, k, n, dt, id=name + ("" if m == DECODE_M else f"-m{m}")
                 + ("-u8" if dt == jnp.uint8 else ""))
    for dt in (jnp.int32, jnp.uint8)
    for m in ((DECODE_M, *SERVED_M) if dt == jnp.int32 else SERVED_M)
    for name, (k, n) in (("up_gate", (D_MODEL, D_FF)),
                         ("down", (D_FF, D_MODEL)))])
def test_approx_matmul_compiles_at_qwen3_mlp_shapes(one_chip, side, M, K, N,
                                                    b_dtype):
    _assert_lut_matmul_compiles(one_chip, M, K, N, side, b_dtype)


@pytest.mark.parametrize("K,N,b_dtype", [
    pytest.param(2048, 5632, jnp.int32, id="up_gate"),
    pytest.param(5632, 2048, jnp.int32, id="down"),
    pytest.param(2048, 5632, jnp.uint8, id="up_gate-u8"),
    pytest.param(5632, 2048, jnp.uint8, id="down-u8")])
def test_w8_approx_matmul_compiles_at_stablelm2_mlp_shapes(one_chip, K, N,
                                                           b_dtype):
    """The 8-bit path at the shapes the StableLM-2-1.6B cell serves, its
    whole 128-row block: K = 5632 is no multiple of the path's 227-wide
    K blocks."""
    _assert_lut_matmul_compiles(one_chip, 128, K, N, 256, b_dtype)


def test_flash_attention_compiles(one_chip):
    q = jax.ShapeDtypeStruct((1, 32, 512, 128), jnp.bfloat16,
                             sharding=one_chip)
    text = _compiled_text(flash_attention_pallas, q, q, q)
    assert "tpu_custom_call" in text


def _population(P, T, n, m, sharding):
    return (jax.ShapeDtypeStruct((P, T, n), jnp.int32, sharding=sharding),
            jax.ShapeDtypeStruct((P, m, T), jnp.int32, sharding=sharding))


def test_template_eval_compiles_at_paper_scale(one_chip):
    P, T, n, m, W = 4096, 16, 8, 8, 8
    lits, sel = _population(P, T, n, m, one_chip)
    text = _compiled_text(
        template_eval_pallas, lits, sel,
        jax.ShapeDtypeStruct((n, W), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((32 * W,), jnp.int32, sharding=one_chip))
    assert "tpu_custom_call" in text


def test_sharded_template_eval_compiles_on_four_chips(topo, monkeypatch):
    """The fleet search's scorer over a 4-chip ``data`` axis: each chip
    runs the kernel on its quarter of the population, with no gather."""
    from repro.core.tensor_search import population_scorer
    from repro.kernels import ops

    # the described chips are not this process's backend, so "auto" would
    # pick the CPU reference: compile the kernel branch the chip takes
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    mesh = Mesh(np.array(topo.devices), ("data",),
                axis_types=(AxisType.Auto,))
    exact = benchmark("mul_i8")
    score = population_scorer(
        jnp.asarray(input_truth_tables(exact.n_inputs)),
        jnp.asarray(exact.eval_words().astype(np.int32)), mesh)
    lits, sel = _population(4096, 16, 8, 8,
                            NamedSharding(mesh, PartitionSpec("data")))
    text = _compiled_text(jax.jit(score), lits, sel)
    assert "tpu_custom_call" in text
    assert "s32[1,1024]" in text      # one quarter of the population each
    assert "all-gather" not in text


@pytest.mark.parametrize("D,Hkv,hd,extra", [
    (2048, 32, 64, dict(qkv_bias=True, rotary_fraction=0.25)),
    (2560, 8, 128, dict(qk_norm=True))], ids=["stablelm2-mha", "qwen3-gqa"])
def test_paged_decode_attention_reads_the_bf16_gather(one_chip, D, Hkv, hd,
                                                      extra):
    """One ``attention_decode_paged`` layer at a batch cell's shapes: 128
    slots of 20 pages of 8 positions, the pool as the engine sizes it.  No
    f32 array as large as the gathered K may appear anywhere in the
    program, nor (GQA) K or V repeated to the 32 query heads; the temp
    holds at most both pools in the page-major layout the scatter and
    gather use and both bf16 gathers, each padded to 128 lanes (an f32
    copy of one gather alone is twice a gather)."""
    H, slots, pages, page = 32, 128, 20, 8
    n_pool = (slots + 1) * pages + 1
    cfg = ModelConfig(name="attn", family="dense", n_layers=1, d_model=D,
                      n_heads=H, n_kv_heads=Hkv, d_ff=4 * D, vocab_size=16,
                      head_dim=hd, **extra)

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = {"wq": spec((D, H * hd)), "wk": spec((D, Hkv * hd)),
         "wv": spec((D, Hkv * hd)), "wo": spec((H * hd, D))}
    if cfg.qk_norm:
        p.update(q_norm=spec((hd,)), k_norm=spec((hd,)))
    if cfg.qkv_bias:
        p.update(bq=spec((H * hd,)), bk=spec((Hkv * hd,)),
                 bv=spec((Hkv * hd,)))
    pool = spec((n_pool, page, Hkv, hd))
    step = jax.jit(functools.partial(attention_decode_paged, cfg),
                   donate_argnums=(2,))
    compiled = step.lower(p, spec((slots, 1, D)), {"kp": pool, "vp": pool},
                          spec((slots,), jnp.int32),
                          spec((slots, pages), jnp.int32),
                          spec((slots,), jnp.bool_)).compile()

    gathered = slots * pages * page * Hkv * hd
    arrays = [(dt, int(np.prod([int(n) for n in dims.split(",")])), dims)
              for dt, dims in re.findall(r"\b(\w+)\[([\d,]+)\]",
                                         compiled.as_text())]
    widened = {f"{dt}[{dims}]" for dt, n, dims in arrays
               if dt == "f32" and n >= gathered}
    assert not widened, f"f32 arrays as large as the gathered K: {widened}"
    repeated = {f"{dt}[{dims}]" for dt, n, dims in arrays
                if H > Hkv and n >= gathered * H // Hkv}
    assert not repeated, f"K/V repeated to the query heads: {repeated}"
    lanes = max(hd, 128) / hd
    bound = 2 * 2 * lanes * (n_pool + slots * pages) * page * Hkv * hd
    assert compiled.memory_analysis().temp_size_in_bytes <= bound


SMOKE_LIB = Path(__file__).resolve().parents[1] / "benchmarks/chip/data/smoke-lib"
WEIGHT_OPS = re.compile(r"stablehlo\.(reduce|convert)\b.*")
TENSOR_2D = re.compile(r"tensor<(\d+)x(\d+)x\w+>")


@pytest.mark.parametrize("arch,bits", [("qwen3-4b", 4), ("stablelm-1.6b", 8)],
                         ids=["qwen3-w4", "stablelm-w8"])
def test_served_step_quantizes_no_mlp_weight(one_chip, monkeypatch, arch,
                                             bits):
    """The continuous engine's step as the chip runs it (the LUT kernel's
    branch), lowered at reduced shapes: no ``reduce`` or ``convert`` has an
    MLP weight-shaped operand, while the same step without the engine's
    prepared weights has both (the weight quantization, per call)."""
    cfg = get_config(arch, reduced=True).with_approx_mlp(bits=bits)
    compiled, exact_area, _ = load_frontier(SMOKE_LIB, bits)
    ladder = PlanLadder.build(compiled, cfg.n_layers, exact_area=exact_area)
    eng = ContinuousServingEngine(
        cfg, init_model(cfg, jax.random.PRNGKey(0)), max_slots=2,
        prompt_len=4, gen_len=2, page_size=4, plan=ladder.plan(0),
        compiled=compiled, exact_area=exact_area)
    eng.start(telemetry=Telemetry())
    eng.submit(Request(rid=0, tokens=np.arange(1, 5, dtype=np.int32)))
    assert eng.step_once()
    # the described chip is not this process's backend, so "auto" would
    # pick the CPU reference: trace again, on the kernel branch the chip
    # takes
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    jax.clear_caches()
    *args, prepared = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        eng._last_step_args)
    weights = {(cfg.d_model, cfg.d_ff), (cfg.d_ff, cfg.d_model)}

    def weight_ops(prepared):
        text = eng._jit_step.lower(*args, prepared).as_text()
        assert "tpu_custom_call" in text
        return [m.group(0) for m in WEIGHT_OPS.finditer(text)
                if {tuple(map(int, t)) for t in TENSOR_2D.findall(m.group(0))}
                & weights]

    assert weight_ops(None), "the per-call step shows no weight quantization"
    assert weight_ops(prepared) == []
