"""Pallas kernels: LUT matmuls — bit-exact emulation of approximate
multiplier netlists, MXU-native, at 4-bit and 8-bit operand widths.

The obvious emulation of ``out[m,n] = Σ_k LUT[a[m,k], b[k,n]]`` is a gather
per (m, k, n) — fast on a GPU's shared memory, slow on TPU.  The TPU form
splits the table by its column code ``y`` into 16 dense contractions that
run on the MXU::

    out[m, n] = Σ_y Σ_k A_y[m, k] · B_y[k, n]
    A_y[m, k] = LUT[a[m, k], y]      (16 VPU selects over the codes of a)
    B_y[k, n] = [b[k, n] == y]       (one compare)

**Grid.**  ``(i, k, j)`` over (row block, K block, column block), all
sequential.  The 16 row-side *table planes* ``A_y`` depend on ``(i, k)``
only, so they are built once, at ``j == 0``, into a VMEM scratch and read
by every column block of that K block; each grid step does only the
compares of its ``b`` block and the contractions.  Column results
accumulate across ``k`` in an int32 VMEM scratch of the whole row block
(one ``(bm, bn)`` slab per column block); the output block of ``(i, j)``
is written at the last ``k``, and its index map stays put until then so
nothing is written back early.  Block sizes come from the shapes: ``bn``
is the largest multiple of 128 up to 512 that divides the 128-padded N,
``bk`` the same up to 256 for K, so a Qwen3-4B MLP call (2560 x 9728 or
9728 x 2560) takes 190 grid steps instead of 1,520.  On a TPU v5e these
caps ran those calls fastest of the sizes tried (128-512 each way);
``block_m`` stays 128.  Each call of a Pallas kernel in a program carries
its own copy of the kernel's code, so the plane building loops over the
16 column codes in a ``fori_loop`` (with it unrolled as well, each of a
decode step's 108 calls carried ~2 MB of code; now ~1 MB); the
contraction stays unrolled, as a loop it ran 1.6x slower on a TPU v5e.

**Exactness.**  Every contraction is one bf16 MXU pass with f32
accumulation.  ``B_y`` is 0/1, and bf16 holds every integer up to 256
exactly, so a table whose entries all lie in ``[0, 255]`` (every exact
4-bit product table: at most 225) contracts in one pass with no
rounding.  A table with an entry outside that range — a 4-bit table
composed from an approximate 2-bit block reaches 15 x 25 = 375 — is split
as ``v = lo + 256·hi`` with ``lo = v & 255`` and ``hi = v >> 8`` (both
exact in bf16 for ``|v| < 2^16``); the ``hi`` planes are stacked under
the ``lo`` planes, so the same contractions carry both, and the kernel
adds ``hi << 8`` after the int32 cast.  Which form runs is decided at run
time from the table's own range: at the first step of each row block the
kernel ORs the 256 entries of the table in SMEM and keeps the flag in
SMEM, so a per-layer table stack stays a traced argument and hot-swaps
without a retrace (:func:`takes_hi_pass` is the same rule on the host).
Per K block each contraction sums ``bk`` terms of magnitude at most 256,
far under 2^24, so the f32 sums are exact; K blocks accumulate in int32,
exact while ``K * max_entry < 2^31`` (see ``WidthSpec.max_k``).

**8-bit (W8A8) path.**  The same split does not scale to 256 codes (256
selects per column code, 256 contractions per block).  But W8A8 tables in
this stack are *composed* — :mod:`repro.precision.compose` builds every
256x256 table as the exact shift-add of one 16x16 tile over operand
nibbles::

    LUT8[a, b] = T[al, bl] + (T[al, bh] + T[ah, bl]) << 4 + T[ah, bh] << 8

so ``Σ_k LUT8[a, b]`` factors into **four 16x16-tile LUT matmuls combined
by shift-add inside the kernel** — each over nibble planes of the codes,
all sharing the one tile already resident in SMEM, through the same
contraction as the 4-bit path (planes per nibble of ``a``, compares per
nibble of ``b``).  The wrapper recovers the tile from the (256, 256)
table by exact integer inversion (:func:`repro.precision.compose.
extract_tile`'s jnp twin below), keeping the public interface "codes +
behaviour table" at every width.  Tables that are *not* composed are out
of contract for the Pallas path (the ``ref`` backend eats them).  The
shift-add runs in f32 per K block, exact while ``255 * block_k * 289 <
2^24`` (the wrapper refuses a larger ``block_k``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_MAX_BN, _MAX_BK = 512, 256   # widest derived bn and bk
_BYTE = 255                   # one pass takes entries in [0, 255]


def _derived_block(padded: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``padded`` (itself a
    multiple of 128) and is at most ``cap``."""
    return max(b for b in range(_LANES, min(cap, padded) + 1, _LANES)
               if padded % b == 0)


def _nibbles(x: jax.Array, n: int) -> list[jax.Array]:
    return [x] if n == 1 else [x & 15, x >> 4]


def _split(v: jax.Array, parts: int) -> list[jax.Array]:
    """A table entry as the values of its planes: itself, or ``lo`` and
    ``hi`` with ``v = lo + 256 * hi``."""
    return [v] if parts == 1 else [v & _BYTE, v >> 8]


def _build_planes(planes_ref, a_ref, lut_ref, nib: int, parts: int
                  ) -> None:
    """``planes_ref[y]`` row block ``p * nib + i`` holds
    ``part_p(LUT[x_i, y])`` in bf16, ``x_i`` the ``i``-th code plane of
    the ``a`` block: 16 selects per column code."""
    bm = a_ref.shape[0]

    def build(y, carry):
        for i, x in enumerate(_nibbles(a_ref[...], nib)):
            vals = [jnp.zeros(x.shape, jnp.float32) for _ in range(parts)]
            for c in range(16):
                hit = x == c
                for p, v in enumerate(_split(lut_ref[c, y], parts)):
                    vals[p] = jnp.where(hit, v.astype(jnp.float32), vals[p])
            for p, v in enumerate(vals):
                r = (p * nib + i) * bm
                planes_ref[y, r:r + bm, :] = v.astype(jnp.bfloat16)
        return carry

    jax.lax.fori_loop(0, 16, build, 0)


def _lut16_contract(planes_ref, ys: list[jax.Array], rows: int
                    ) -> list[jax.Array]:
    """``S[j] = Σ_y planes[y, :rows] · [ys[j] == y]``: per column code one
    compare per ``ys`` plane and one single-pass bf16 contraction of all
    the stacked row-side planes (every part of every code plane of ``a``),
    so row block ``p * nib + i`` of ``S[j]`` is
    ``Σ_k part_p(LUT[x_i[m,k], ys[j][k,n]])``."""
    S = [None] * len(ys)
    for y in range(16):
        plane = planes_ref[y, :rows, :]
        for j, q in enumerate(ys):
            onehot = jnp.where(q == y, 1.0, 0.0).astype(jnp.bfloat16)
            d = jax.lax.dot_general(plane, onehot, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            S[j] = d if S[j] is None else S[j] + d
    return S


def _kernel(lut_ref, a_ref, b_ref, out_ref, planes_ref, acc_ref, wide_ref,
            *, nib: int):
    """One grid step ``(i, k, j)``; ``nib`` is 1 (4-bit codes) or 2 (8-bit
    codes as two nibble planes, contracted against the 16x16 tile)."""
    k, j = pl.program_id(1), pl.program_id(2)
    bm = a_ref.shape[0]

    @pl.when((k == 0) & (j == 0))
    def _range():
        bits = jnp.int32(0)
        for x in range(16):
            for y in range(16):
                bits = bits | lut_ref[x, y]
        wide_ref[0] = bits & ~_BYTE

    @pl.when(k == 0)
    def _zero():
        acc_ref[j] = jnp.zeros(acc_ref.shape[1:], jnp.int32)

    def step(parts: int):
        @pl.when(j == 0)
        def _planes():
            _build_planes(planes_ref, a_ref, lut_ref, nib, parts)

        S = _lut16_contract(planes_ref, _nibbles(b_ref[...], nib),
                            parts * nib * bm)
        total = None
        for p in range(parts):
            s = [[S[jj][(p * nib + i) * bm:(p * nib + i + 1) * bm]
                  for jj in range(nib)] for i in range(nib)]
            if nib == 1:
                [[acc]] = s
            else:   # shift-add with f32-exact weights (see the guard)
                (s_ll, s_lh), (s_hl, s_hh) = s
                acc = s_ll + (s_lh + s_hl) * 16.0 + s_hh * 256.0
            acc = acc.astype(jnp.int32)
            total = acc if p == 0 else total + (acc << 8)
        acc_ref[j] += total

    wide = wide_ref[0] != 0
    pl.when(wide)(lambda: step(2))
    pl.when(jnp.logical_not(wide))(lambda: step(1))

    @pl.when(k == pl.num_programs(1) - 1)
    def _emit():
        out_ref[...] = acc_ref[j]


def takes_hi_pass(lut) -> bool:
    """Whether the kernel runs its second (``hi``) pass for this table:
    some entry of the table it contracts (for a 256x256 table, the 16x16
    tile) lies outside ``[0, 255]``.  Host-side, on a numpy table, the
    same rule the kernel applies to the table in SMEM."""
    t = np.asarray(lut, dtype=np.int64)
    if t.shape[-1] == 256:
        t = _extract_tile_jnp(t)
    return bool(np.any(t & ~_BYTE))


def _extract_tile_jnp(lut: jax.Array) -> jax.Array:
    """jnp twin of :func:`repro.precision.compose.extract_tile` — exact
    integer inversion of the nibble shift-add for composed tables; runs
    inside the jitted wrapper so the (256, 256) stack entry stays the
    swap unit (and on numpy tables in :func:`takes_hi_pass`)."""
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
    block_n: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """``block_n`` and ``block_k`` default to the derived sizes (module
    docstring); given, they are used as they are."""
    side = lut.shape[-1]
    M, K = a.shape
    _, N = b.shape
    kp, np_ = -(-K // _LANES) * _LANES, -(-N // _LANES) * _LANES
    if side == 16:
        nib, table = 1, lut
        block_k = block_k or _derived_block(kp, _MAX_BK)
    elif side == 256:
        # the 8-bit kernel consumes the 16x16 generator tile; recover it
        # from the composed table (exact for anything compose.py emits)
        nib, table = 2, _extract_tile_jnp(lut)
        # per-block f32 exactness bound: acc <= 255 * block_k * 289 must
        # stay under 2^24 or the shift-add rounds before the int32 cast,
        # silently breaking the bit-match-the-oracle contract
        max_bk = (1 << 24) // (255 * 289)
        block_k = block_k or _derived_block(kp, max_bk)
        if block_k > max_bk:
            raise ValueError(
                f"block_k {block_k} exceeds the 8-bit path's f32-exact "
                f"accumulation bound ({max_bk}); pick a smaller K block"
            )
    else:
        raise ValueError(f"unsupported LUT side {side}; expected 16 or 256")
    block_n = block_n or _derived_block(np_, _MAX_BN)

    pm, pn, pk = (-M) % block_m, (-N) % block_n, (-K) % block_k
    # K padding uses code 0; LUT[0, 0] may be nonzero for an approximate
    # netlist (and a composed 8-bit table contributes exactly
    # LUT[0, 0] = 289 * T[0, 0] per padded k), so the padded-K
    # contribution is subtracted analytically below.
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, pk), (0, pn)))
    nk, nj = (K + pk) // block_k, (N + pn) // block_n
    grid = ((M + pm) // block_m, nk, nj)

    out = pl.pallas_call(
        functools.partial(_kernel, nib=nib),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_m, block_k), lambda i, k, j: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, k, j: (k, j)),
        ],
        # parked on column block 0 until the last K block writes each one
        out_specs=pl.BlockSpec(
            (block_m, block_n),
            lambda i, k, j: (i, jnp.where(k == nk - 1, j, 0))),
        out_shape=jax.ShapeDtypeStruct((M + pm, N + pn), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((16, 2 * nib * block_m, block_k), jnp.bfloat16),
            pltpu.VMEM((nj, block_m, block_n), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=interpret,
    )(table, a, b)
    out = out[:M, :N]
    if pk:  # remove the LUT[0,0] contribution of the K padding
        out = out - jnp.int32(pk) * lut[0, 0]
    return out
