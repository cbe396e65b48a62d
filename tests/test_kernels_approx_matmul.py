"""Pallas approx_matmul (bitplane/one-hot MXU formulation) vs gather oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.approx_matmul import takes_hi_pass
from repro.precision import compose
from repro.quant import approx_linear, build_lut, exact_mul_lut, quantize_int4
from repro.core.arith import benchmark


def _table(kind, rng):
    """A 16x16 table of the given kind (see the cases below)."""
    if kind == "wide":        # entries up to 4095: the kernel's hi pass
        return rng.integers(0, 4096, size=(16, 16))
    if kind == "composed":    # a 2-bit operator whose outputs reach 15
        block = rng.integers(0, 16, size=(4, 4))
        block[3, 3] = 15
        return compose.compose_table(block, "mul", 2, 4)   # max 375
    lut = rng.integers(0, 226, size=(16, 16))
    if kind == "lut00":       # K padding must remove a nonzero LUT[0, 0]
        lut[0, 0] = 201
    return lut


@pytest.mark.parametrize("M,K,N,kind", [
    pytest.param(8, 16, 8, "rand", id="8-16-8"),
    pytest.param(37, 53, 29, "rand", id="37-53-29"),   # padding paths
    pytest.param(128, 128, 128, "rand", id="128-128-128"),  # exact fit
    pytest.param(130, 257, 64, "rand", id="130-257-64"),
    # N off the derived bn (700 -> 768 = 2 x 384), three K blocks of 128
    pytest.param(64, 300, 700, "wide", id="64-300-700-wide"),
    pytest.param(128, 512, 640, "composed", id="128-512-640-composed"),
    pytest.param(8, 200, 1100, "lut00", id="8-200-1100-lut00"),
    # tables swapped under one jitted call: one trace for all of them
    pytest.param(64, 384, 520, "swap", id="64-384-520-swap"),
])
def test_matches_gather_oracle(M, K, N, kind, rng):
    a = rng.integers(0, 16, size=(M, K)).astype(np.int32)
    b = rng.integers(0, 16, size=(K, N)).astype(np.int32)
    kinds = ["rand", "composed", "wide", "rand"] if kind == "swap" else [kind]
    traces = []

    @jax.jit
    def pallas(a, b, lut):
        traces.append(1)
        return ops.approx_matmul(a, b, lut, backend="pallas_interpret")

    for k in kinds:
        lut = _table(k, rng).astype(np.int32)
        gt = lut[a[:, :, None], b[None, :, :]].sum(axis=1)
        o_ref = np.asarray(ref.approx_matmul(
            jnp.asarray(a), jnp.asarray(b), jnp.asarray(lut)))
        o_pal = np.asarray(pallas(jnp.asarray(a), jnp.asarray(b),
                                  jnp.asarray(lut)))
        assert np.array_equal(o_ref, gt)
        assert np.array_equal(o_pal, gt), k
        assert takes_hi_pass(lut) == (k in ("wide", "composed"))
    assert len(traces) == 1


def test_exact_lut_reproduces_int_matmul(rng):
    """With the exact product table, the LUT matmul IS an int matmul."""
    lut = exact_mul_lut()
    a = rng.integers(0, 16, size=(24, 48)).astype(np.int32)
    b = rng.integers(0, 16, size=(48, 16)).astype(np.int32)
    out = np.asarray(ops.approx_matmul(
        jnp.asarray(a), jnp.asarray(b), jnp.asarray(lut), backend="ref"))
    assert np.array_equal(out, a @ b)


def test_lut_built_from_exact_circuit_is_exact():
    lut = build_lut(benchmark("mul_i8"))
    assert np.array_equal(lut, exact_mul_lut())


def test_approx_linear_signed_decomposition(rng):
    """Signed int4 x int4 through the unsigned multiplier + exact correction
    equals the plain quantized matmul when the LUT is exact."""
    x = rng.standard_normal((5, 32)).astype(np.float32)
    w = rng.standard_normal((32, 7)).astype(np.float32)
    lut = jnp.asarray(exact_mul_lut())
    got = np.asarray(approx_linear(jnp.asarray(x), jnp.asarray(w), lut, backend="ref"))
    xq, sx = quantize_int4(jnp.asarray(x), axis=-1)
    wq, sw = quantize_int4(jnp.asarray(w), axis=0)
    want = np.asarray(
        ((np.asarray(xq) - 8) @ (np.asarray(wq) - 8)).astype(np.float32)
        * np.asarray(sx) * np.asarray(sw)
    )
    assert np.allclose(got, want, rtol=1e-5, atol=1e-5)
