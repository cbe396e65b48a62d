"""Symmetric integer quantization + approximate-multiplier linear layers.

Signed b-bit activations/weights run on an *unsigned* bxb approximate
multiplier via the exact shift decomposition (``c = 2**(b-1)``)::

    (a' - c)(b' - c) = a'b' - c a' - c b' + c²,   a', b' in [0, 2**b)

Only the ``a'b'`` term goes through the (approximate) multiplier; the
correction terms are exact adder work — on real silicon these are the
cheap operators, and in emulation they are exact integer sums.  This is
how edge NN inference actually deploys the paper's unsigned multipliers
for signed tensors (DESIGN.md §3), and it is width-generic: the W4A4
regime uses ``c = 8`` with a 16x16 table, W8A8 uses ``c = 128`` with a
composed 256x256 table.  :func:`approx_linear` infers the width from the
table it is handed (shapes are static under jit, so width dispatch never
retraces on a hot-swap at a fixed width).

The weight side of that arithmetic — per-column codes, scales and code
sums — is a pure function of the weight and the width, so a server makes
it once: :func:`prepare_weight` returns a :class:`PreparedWeight` (codes
stored at one byte each, which the LUT kernel reads as stored), and
:func:`approx_linear` takes either that or the float weight, which it
prepares inside the same trace.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ..kernels import ops
from ..precision.widths import NATIVE_BLOCK_BITS, get_width, width_from_lut


def quantize_intb(x: jax.Array, bits: int, axis: int = -1
                  ) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-slice b-bit quantization shared by every width:
    returns (codes in ``[0, 2**bits)``, scale).

    ``x ≈ (codes - 2**(bits-1)) * scale``; codes are biased-unsigned for
    the LUT (the symmetric range leaves code 0 unused).
    """
    w = get_width(bits)
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / w.qmax, 1.0)
    q = jnp.clip(jnp.round(x / scale), -w.qmax, w.qmax).astype(jnp.int32)
    return q + w.bias, scale


def quantize_int4(x: jax.Array, axis: int = -1) -> tuple[jax.Array, jax.Array]:
    """The historical 4-bit entry point (kept for callers and tests)."""
    return quantize_intb(x, NATIVE_BLOCK_BITS, axis=axis)


def dequantize(codes: jax.Array, scale: jax.Array,
               bits: int = NATIVE_BLOCK_BITS) -> jax.Array:
    bias = get_width(bits).bias
    return (codes.astype(jnp.float32) - float(bias)) * scale


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["codes", "scale", "colsum"],
                   meta_fields=["bits"])
@dataclasses.dataclass(frozen=True)
class PreparedWeight:
    """A ``(..., K, N)`` weight as the LUT matmul consumes it at ``bits``:
    biased codes in ``[0, 2**bits)`` stored as uint8 ``(..., K, N)``, the
    per-column scales ``(..., 1, N)`` f32 and the per-column code sums
    ``(..., 1, N)`` int32 of the bias correction.  A pytree, so a stack of
    them rides through ``jax.jit`` as arguments and indexes per layer with
    ``jax.tree.map``; ``bits`` is static."""

    codes: jax.Array
    scale: jax.Array
    colsum: jax.Array
    bits: int


def prepare_weight(w: jax.Array, bits: int) -> PreparedWeight:
    """The weight side of :func:`approx_linear` for a ``(..., K, N)``
    float weight (per column of each ``(K, N)`` matrix), bit-identical to
    what the per-call form computes.  Under one ``jax.jit`` the round,
    clip and cast fuse, so no int32 copy of the codes is materialized."""
    wq, sw = quantize_intb(w, bits, axis=-2)
    return PreparedWeight(codes=wq.astype(jnp.uint8),
                          scale=sw.astype(jnp.float32),
                          colsum=wq.sum(axis=-2, keepdims=True),
                          bits=bits)


def approx_linear(
    x: jax.Array,                    # (..., K) float
    w: jax.Array | PreparedWeight,   # (K, N) float, or prepared
    lut: jax.Array,   # (side, side) int32 approximate product table
    *,
    backend: str = "auto",
) -> jax.Array:
    """``x @ w`` through the approximate b-bit multiplier, bit-exact
    emulation at the width the table implies (16x16 -> W4A4,
    256x256 -> W8A8).

    Per-row activation scales, per-column weight scales (standard WbAb).
    ``w`` is either a :class:`PreparedWeight` at the table's width, made
    once by the caller (the serving engines make one per MLP matrix when
    they are built), or a float weight, which is prepared here, in the
    same trace, on every call (the profiler, the full forward, training);
    the arithmetic is the same either way.  The activation quantization,
    any per-call weight preparation, the bias correction and the rescale
    sit under ``jax.named_scope("quantize")``; the kernel call with its
    wrapper work (padding, tile extraction, output conversion) under
    ``lut.w<bits>`` (``lut.w4``, ``lut.w8``).
    """
    spec = width_from_lut(lut)
    lead = x.shape[:-1]
    K = x.shape[-1]
    x2 = x.reshape(-1, K)
    with jax.named_scope("quantize"):
        xq, sx = quantize_intb(x2, spec.bits, axis=-1)    # (M, K), (M, 1)
        if not isinstance(w, PreparedWeight):
            w = prepare_weight(w, spec.bits)
    if w.bits != spec.bits:
        raise ValueError(f"weight prepared at {w.bits} bits, table is "
                         f"{spec.bits}-bit")

    with jax.named_scope(f"lut.w{spec.bits}"):
        raw = ops.approx_matmul(xq, w.codes, lut,
                                backend=backend).astype(jnp.float32)
    with jax.named_scope("quantize"):
        # exact correction of the biased-unsigned decomposition
        c = float(spec.bias)
        sum_a = xq.sum(axis=1, keepdims=True).astype(jnp.float32)  # (M, 1)
        sum_b = w.colsum.astype(jnp.float32)                        # (1, N)
        corrected = raw - c * sum_a - c * sum_b + c * c * K
        out = corrected * sx * w.scale
    return out.reshape(*lead, w.codes.shape[-1]).astype(x.dtype)
