"""Pallas kernels: LUT matmuls — bit-exact emulation of approximate
multiplier netlists, MXU-native, at 4-bit and 8-bit operand widths.

The obvious emulation of ``out[m,n] = Σ_k LUT[a[m,k], b[k,n]]`` is a gather
per (m, k, n) — fast on a GPU's shared memory, slow on TPU.  The TPU form
splits the table by its column code ``y`` into 16 dense contractions that
run on the MXU::

    out[m, n] = Σ_y Σ_k A_y[m, k] · B_y[k, n]
    A_y[m, k] = LUT[a[m, k], y]      (16 VPU selects over the codes of a)
    B_y[k, n] = [b[k, n] == y]       (one compare)

Every operand stays a 2-D (sublane, lane) tile of its block, so Mosaic
never has to fold a sublane axis into lanes.  The table rides in SMEM and
its entries are read as scalars.

**8-bit (W8A8) path.**  The same split does not scale to 256 codes (256
selects per column code, 256 contractions per block).  But W8A8 tables in
this stack are *composed* — :mod:`repro.precision.compose` builds every
256x256 table as the exact shift-add of one 16x16 tile over operand
nibbles::

    LUT8[a, b] = T[al, bl] + (T[al, bh] + T[ah, bl]) << 4 + T[ah, bh] << 8

so ``Σ_k LUT8[a, b]`` factors into **four 16x16-tile LUT matmuls combined
by shift-add inside the kernel** — each over nibble planes of the codes,
all sharing the one tile already resident in SMEM.  The wrapper recovers
the tile from the (256, 256) table by exact integer inversion
(:func:`repro.precision.compose.extract_tile`'s jnp twin below), keeping
the public interface "codes + behaviour table" at every width — the
per-layer serving stack stays a plain jitted argument and hot-swaps
without retracing.  Tables that are *not* composed are out of contract
for the Pallas path (the ``ref`` backend eats them).

Accumulation: per k-block the contractions are exact in f32 (tile entries
<= 255, block_k <= 128 ⇒ partial sums < 2^24 even through the x289 shift
weights); blocks accumulate in int32, exact while
``K * max_entry * 289 < 2^31`` (see ``WidthSpec.max_k``).  The K
dimension is tiled by the grid's sequential last axis; the accumulator
lives in the output block (revisited across k steps).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lut16_contract(xs: list[jax.Array], ys: list[jax.Array], lut_ref
                    ) -> list[list[jax.Array]]:
    """``S[i][j][m, n] = Σ_k LUT[xs[i][m,k], ys[j][k,n]]`` for 4-bit code
    planes, one MXU contraction per column code and plane pair; the 4-bit
    kernel passes one plane each, the 8-bit kernel two nibble planes each,
    so every plane's selects and compares are built once.  ``lut_ref`` is
    the (16, 16) f32 table in SMEM."""
    x_is = [[x == c for c in range(16)] for x in xs]
    bm, bn = xs[0].shape[0], ys[0].shape[1]
    acc = [[jnp.zeros((bm, bn), jnp.float32) for _ in ys] for _ in xs]
    for col in range(16):
        a_cols = []
        for masks in x_is:
            a_col = jnp.zeros(xs[0].shape, jnp.float32)
            for c in range(16):
                a_col = jnp.where(masks[c], lut_ref[c, col], a_col)
            a_cols.append(a_col)
        b_cols = [(y == col).astype(jnp.float32) for y in ys]
        for i, a_col in enumerate(a_cols):
            for j, b_col in enumerate(b_cols):
                acc[i][j] += jax.lax.dot_general(
                    a_col, b_col, (((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32,
                )
    return acc


def _kernel(a_ref, b_ref, lut_ref, out_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    [[acc]] = _lut16_contract([a_ref[...]], [b_ref[...]], lut_ref)
    out_ref[...] += acc.astype(jnp.int32)


def _kernel8(a_ref, b_ref, tile_ref, out_ref):
    """Two-level 8-bit form: four nibble-plane tile matmuls + shift-add."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[...]          # (bm, bk) int32 in [0, 256)
    b = b_ref[...]          # (bk, bn) int32 in [0, 256)
    (s_ll, s_lh), (s_hl, s_hh) = _lut16_contract(
        [a & 15, a >> 4], [b & 15, b >> 4], tile_ref)
    # shift-add with f32-exact weights (partials < 2^24 per k-block)
    acc = s_ll + (s_lh + s_hl) * 16.0 + s_hh * 256.0
    out_ref[...] += acc.astype(jnp.int32)


def _extract_tile_jnp(lut: jax.Array) -> jax.Array:
    """jnp twin of :func:`repro.precision.compose.extract_tile` — exact
    integer inversion of the nibble shift-add for composed tables; runs
    inside the jitted wrapper so the (256, 256) stack entry stays the
    swap unit."""
    t00 = lut[0, 0] // 289
    tx0 = (lut[:16, 0] - 272 * t00) // 17
    t0y = (lut[0, :16] - 272 * t00) // 17
    return lut[:16, :16] - 16 * (tx0[:, None] + t0y[None, :]) - 256 * t00


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def approx_matmul_pallas(
    a: jax.Array,    # (M, K) int32 in [0, side)
    b: jax.Array,    # (K, N) int32 in [0, side)
    lut: jax.Array,  # (side, side) int32; side = 16 (4-bit) or 256 (8-bit)
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    side = lut.shape[-1]
    if side == 16:
        kernel, table = _kernel, lut
    elif side == 256:
        # the 8-bit kernel consumes the 16x16 generator tile; recover it
        # from the composed table (exact for anything compose.py emits)
        kernel, table = _kernel8, _extract_tile_jnp(lut)
        # per-block f32 exactness bound: acc <= 255 * block_k * 289 must
        # stay under 2^24 or the shift-add rounds before the int32 cast,
        # silently breaking the bit-match-the-oracle contract
        max_bk = (1 << 24) // (255 * 289)
        if block_k > max_bk:
            raise ValueError(
                f"block_k {block_k} exceeds the 8-bit path's f32-exact "
                f"accumulation bound ({max_bk}); pick a smaller K block"
            )
    else:
        raise ValueError(f"unsupported LUT side {side}; expected 16 or 256")

    M, K = a.shape
    _, N = b.shape
    pm, pn, pk = (-M) % block_m, (-N) % block_n, (-K) % block_k
    # K padding uses code 0; LUT[0, 0] may be nonzero for an approximate
    # netlist (and a composed 8-bit table contributes exactly
    # LUT[0, 0] = 289 * T[0, 0] per padded k), so the padded-K
    # contribution is subtracted analytically below.
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, pk), (0, pn)))
    grid = ((M + pm) // block_m, (N + pn) // block_n, (K + pk) // block_k)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M + pm, N + pn), jnp.int32),
        interpret=interpret,
    )(a, b, table.astype(jnp.float32))
    out = out[:M, :N]
    if pk:  # remove the LUT[0,0] contribution of the K padding
        out = out - jnp.int32(pk) * lut[0, 0]
    return out
