"""Pallas kernel: bit-packed shared-template population evaluation.

This is the compute hot-spot of the beyond-paper *tensorized ALS search*
(DESIGN.md §4): thousands of candidate parameter assignments are scored
against the full input space per generation.  The ∀-inputs sweep is
bit-packed — one 32-bit word carries 32 input assignments — so a
candidate's products/sums are evaluated with word-wide VPU boolean ops, and
the per-assignment integer re-interpretation (the miter's ``map``) is an
unrolled shift/mask loop over the (static, <= 8) packed words.

Layout: the population runs along lanes.  The wrapper transposes the
candidates to ``lits`` (n, T, P) and ``sel`` (T, m, P), so every
per-literal and per-product access in the kernel is a leading-axis ref
slice of a 2-D (sublane, lane) tile; the packed truth tables are scalars
in SMEM.  Words are handled as int32 bit patterns (logical shifts), and
the outputs are (1, P) rows.

Tiling: the grid runs over population blocks; each block holds the full
(T, n, m, W) problem — for paper-scale operators (n <= 8, T <= 16, m <= 8,
W <= 8) the per-block working set is a few hundred KB, far below VMEM.
All loops over n / T / m / W are static (unrolled at trace time).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

USE, NEG = 0, 1


def _kernel(
    lits_ref,   # (n, T, Pb) int32
    sel_ref,    # (T, m, Pb) int32
    tt_ref,     # (n, W) int32 bit patterns, SMEM
    ev_ref,     # (W, 32, 1) int32 (padded with zeros past S)
    wce_ref,    # (1, Pb) int32 — worst-case error
    sum_ref,    # (1, Pb) int32 — total error over all assignments
    *,
    n: int,
    T: int,
    m: int,
    W: int,
    S: int,
):
    Pb = lits_ref.shape[-1]
    use = [lits_ref[j] == USE for j in range(n)]      # (T, Pb) each
    neg = [lits_ref[j] == NEG for j in range(n)]
    sel = [sel_ref[t] > 0 for t in range(T)]          # (m, Pb) each
    bit = jax.lax.broadcasted_iota(jnp.int32, (32, Pb), 0)
    wce = jnp.zeros((1, Pb), jnp.int32)
    esum = jnp.zeros((1, Pb), jnp.int32)
    for w in range(W):
        # ---- products: AND over selected literals (bit-packed) -------------
        prods = jnp.full((T, Pb), -1, jnp.int32)
        for j in range(n):
            word = tt_ref[j, w]
            prods = (prods & jnp.where(use[j], word, -1)
                     & jnp.where(neg[j], ~word, -1))
        # ---- sums: OR over selected products --------------------------------
        outs = jnp.zeros((m, Pb), jnp.int32)
        for t in range(T):
            outs = outs | jnp.where(sel[t], prods[t:t + 1, :], 0)
        # ---- map + dist: per-assignment value, worst-case |err| ------------
        vals = jnp.zeros((32, Pb), jnp.int32)
        for i in range(m):
            row = jnp.broadcast_to(outs[i:i + 1, :], (32, Pb))
            vals = vals + ((jax.lax.shift_right_logical(row, bit) & 1) << i)
        err = jnp.abs(vals - ev_ref[w])
        # mask assignments past the real input-space size S
        err = jnp.where(32 * w + bit < S, err, 0)
        wce = jnp.maximum(wce, err.max(axis=0, keepdims=True))
        esum = esum + err.sum(axis=0, keepdims=True)
    wce_ref[...] = wce
    sum_ref[...] = esum


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def template_eval_pallas(
    lits: jax.Array,        # (P, T, n) int32
    sel: jax.Array,         # (P, m, T) int32
    in_tt: jax.Array,       # (n, W) uint32
    exact_vals: jax.Array,  # (S,) int32
    *,
    block_p: int = 256,
    interpret: bool = False,
) -> jax.Array:
    P, T, n = lits.shape
    m = sel.shape[1]
    W = in_tt.shape[1]
    S = exact_vals.shape[0]

    pad = (-P) % block_p
    lits_t = jnp.pad(lits.transpose(2, 1, 0), ((0, 0), (0, 0), (0, pad)))
    sel_t = jnp.pad(sel.transpose(2, 1, 0), ((0, 0), (0, 0), (0, pad)))
    tt = jax.lax.bitcast_convert_type(in_tt, jnp.int32)
    ev = jnp.pad(exact_vals.astype(jnp.int32), (0, W * 32 - S))

    wce, esum = pl.pallas_call(
        functools.partial(_kernel, n=n, T=T, m=m, W=W, S=S),
        grid=((P + pad) // block_p,),
        in_specs=[
            pl.BlockSpec((n, T, block_p), lambda i: (0, 0, i)),
            pl.BlockSpec((T, m, block_p), lambda i: (0, 0, i)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((W, 32, 1), lambda i: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_p), lambda i: (0, i)),
            pl.BlockSpec((1, block_p), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, P + pad), jnp.int32),
            jax.ShapeDtypeStruct((1, P + pad), jnp.int32),
        ],
        interpret=interpret,
    )(lits_t, sel_t, tt, ev.reshape(W, 32, 1))
    return wce[0, :P], esum[0, :P]
