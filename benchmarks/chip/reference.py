"""The plain reference: a float32 ``jax.numpy`` forward of the served
decoder, with the MLP's matmuls through the plan's LUT products.

It imports nothing of the program.  It follows the block the program
serves (the configuration file lists where that block departs from the
published model): RMSNorm with gain ``1 + g``, q/k RMSNorm when the model
has it, half-split rotary over the whole head, causal GQA attention, a
SwiGLU MLP whose three matmuls run W``b``A``b`` through the layer's
product table, and a head tied to the embedding or not.

The weights are rebuilt from the seed layer by layer
(:mod:`benchmarks.chip.weights`), so the reference holds one layer at a
time beside the activations.  Tables come from the configuration file's
``lut_plan``: a 4-bit layer names a 16x16 table (exact: ``a * b``); an
8-bit layer names the 16x16 tile its 256x256 table is composed from,
``T8[a, b] = T[al, bl] + 16 (T[al, bh] + T[ah, bl]) + 256 T[ah, bh]``.

``mode="f32"`` is the reference.  ``mode="fp8"`` is its control: every
matmul outside the LUT path takes float8 (e4m3) operands with per-tensor
scales, the precision step below the bf16 the configuration states.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .weights import (Dims, base_key, final_norm, head_weights,
                      embed_weights, layer_weights)

E4M3_MAX = 448.0


def exact_tile() -> np.ndarray:
    a = np.arange(16, dtype=np.int64)
    return a[:, None] * a[None, :]


def compose8(tile: np.ndarray) -> np.ndarray:
    """The 256x256 table a 16x16 tile composes to."""
    a = np.arange(256)
    lo, hi = a & 15, a >> 4
    t = np.asarray(tile, np.int64)
    return (t[lo[:, None], lo[None, :]]
            + 16 * (t[lo[:, None], hi[None, :]] + t[hi[:, None], lo[None, :]])
            + 256 * t[hi[:, None], hi[None, :]])


def layer_tiles(doc: dict) -> np.ndarray:
    """``(L, 16, 16)``: each layer's 4-bit table, or the tile of its
    8-bit table, from the configuration's ``lut_plan``."""
    plan = doc["lut_plan"]
    named = plan.get("tables") or plan.get("tiles") or {}
    return np.stack([exact_tile() if key is None
                     else np.asarray(named[key], np.int64)
                     for key in plan["layers"]])


def served_tables(doc: dict) -> np.ndarray:
    """``(L, side, side)``: the stack the program must serve."""
    tiles = layer_tiles(doc)
    if int(doc["approx_bits"]) == 4:
        return tiles
    return np.stack([compose8(t) for t in tiles])


# --------------------------------------------------------------- LUT matmul
def _lut_sum16(a, b, tile):
    """``S[m, n] = sum_k tile[a[m, k], b[k, n]]`` for codes in [0, 16).

    One-hot rows of ``a`` against the tile gathered at ``b``: both
    operands are integers of at most 8 significant bits, exact in bf16,
    and every partial sum stays under 2**24, so a bf16 matmul with f32
    accumulation is exact here."""
    def body(c, acc):
        lhs = (a == c).astype(jnp.bfloat16)
        rhs = tile[c][b].astype(jnp.bfloat16)
        return acc + jnp.dot(lhs, rhs, preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(0, 16, body,
                            jnp.zeros((a.shape[0], b.shape[1]), jnp.float32))
    return acc.astype(jnp.int32)


def lut_sum(a, b, tile, bits: int):
    """``sum_k LUT[a[m, k], b[k, n]]`` in int32 at 4 or 8 bits."""
    if bits == 4:
        return _lut_sum16(a, b, tile)
    al, ah, bl, bh = a & 15, a >> 4, b & 15, b >> 4
    return (_lut_sum16(al, bl, tile)
            + 16 * (_lut_sum16(al, bh, tile) + _lut_sum16(ah, bl, tile))
            + 256 * _lut_sum16(ah, bh, tile))


def quantize(x, bits: int, axis: int):
    """Symmetric codes in ``[0, 2**bits)`` and the scale, per slice."""
    bias = 1 << (bits - 1)
    qmax = bias - 1
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int32)
    return q + bias, scale


def lut_linear(x, w, tile, bits: int):
    """``x @ w`` through the multiplier table, x (M, K), w (K, N), f32."""
    bias = 1 << (bits - 1)
    xq, sx = quantize(x, bits, axis=1)
    wq, sw = quantize(w, bits, axis=0)
    raw = lut_sum(xq, wq, tile, bits)
    k = x.shape[1]
    corr = (raw - bias * xq.sum(axis=1, keepdims=True)
            - bias * wq.sum(axis=0, keepdims=True) + bias * bias * k)
    return corr.astype(jnp.float32) * sx * sw


# ------------------------------------------------------------------ dense
def round_e4m3(x):
    """``x`` rounded to the nearest float8 e4m3 value (3 mantissa bits,
    normal exponents from -6, steps of 2**-9 below), kept in f32.  Done
    in arithmetic, so that no compiler can fold a cast pair away."""
    mag = jnp.abs(x)
    _, e = jnp.frexp(jnp.maximum(mag, 2.0 ** -6))
    step = jnp.ldexp(jnp.float32(1.0), e - 4)      # 3 mantissa bits
    q = jnp.minimum(jnp.round(mag / step) * step, E4M3_MAX)
    return jnp.sign(x) * q


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return round_e4m3(x / s), s


def einsum(spec: str, a, b, mode: str):
    hi = jax.lax.Precision.HIGHEST
    if mode == "f32":
        return jnp.einsum(spec, a, b, precision=hi)
    (a8, sa), (b8, sb) = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a8, b8, precision=hi) * (sa * sb)


def rmsnorm(x, g, eps: float):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g)


def rope(x, theta: float):
    """Half-split rotary at positions 0..S-1; x (B, S, H, hd)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def block(d: Dims, p: dict, x, tile, mode: str):
    """One decoder layer over whole sequences, x (B, S, D) f32."""
    B, S, D = x.shape
    H, Hkv, hd = d.heads, d.kv_heads, d.head_dim
    a = p["attn"]
    h = rmsnorm(x, p["ln1"], d.norm_eps)
    q = einsum("bsd,df->bsf", h, a["wq"], mode).reshape(B, S, H, hd)
    k = einsum("bsd,df->bsf", h, a["wk"], mode).reshape(B, S, Hkv, hd)
    v = einsum("bsd,df->bsf", h, a["wv"], mode).reshape(B, S, Hkv, hd)
    if d.qk_norm:
        q = rmsnorm(q, a["q_norm"], d.norm_eps)
        k = rmsnorm(k, a["k_norm"], d.norm_eps)
    q, k = rope(q, d.rope_theta), rope(k, d.rope_theta)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    scores = einsum("bqhd,bkhd->bhqk", q, k, mode) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ctx = einsum("bhqk,bkhd->bqhd", probs, v, mode).reshape(B, S, H * hd)
    x = x + einsum("bsf,fd->bsd", ctx, a["wo"], mode)

    f = p["ffn"]
    h = rmsnorm(x, p["ln2"], d.norm_eps).reshape(B * S, D)
    up = lut_linear(h, f["w1"], tile, d.lut_bits)
    gate = lut_linear(h, f["w3"], tile, d.lut_bits)
    out = lut_linear(jax.nn.silu(up) * gate, f["w2"], tile, d.lut_bits)
    return x + out.reshape(B, S, D)


def forward(d: Dims, seed: int, tokens: np.ndarray, tiles: np.ndarray,
            mode: str = "f32") -> jax.Array:
    """Logits ``(B, S, vocab)`` f32 for token rows ``(B, S)``; rows are
    causal, so padding at a row's end does not touch its earlier logits."""
    base = base_key(seed)
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    make_layer = jax.jit(lambda key: f32(layer_weights(d, key)))
    run_layer = jax.jit(lambda p, x, t: block(d, p, x, t, mode))
    x = jax.jit(lambda b, t: embed_weights(d, b).astype(jnp.float32)[t])(
        base, jnp.asarray(tokens))
    for i in range(d.layers):
        p = make_layer(jax.random.fold_in(base, i))
        x = run_layer(p, x, jnp.asarray(tiles[i], jnp.int32))
    head = jax.jit(lambda b, x: einsum(
        "bsd,dv->bsv",
        rmsnorm(x, final_norm(d, b).astype(jnp.float32), d.norm_eps),
        head_weights(d, b).astype(jnp.float32), mode))
    return head(base, x)


@jax.jit
def token_gaps(ref_logits, picks):
    """How far below the reference's best logit each picked token lies:
    ``max_v ref[b, s, v] - ref[b, s, picks[b, s]]``."""
    best = ref_logits.max(axis=-1)
    taken = jnp.take_along_axis(ref_logits, picks[..., None], axis=-1)[..., 0]
    return best - taken


def batch_rows(seqs: list[np.ndarray]) -> np.ndarray:
    """Right-padded ``(B, S)`` token rows."""
    S = max(len(s) for s in seqs)
    rows = np.zeros((len(seqs), S), np.int32)
    for i, s in enumerate(seqs):
        rows[i, :len(s)] = s
    return rows


def served_gaps(d: Dims, seed: int, tiles: np.ndarray, prompts, generated,
                control: bool = False):
    """Gaps of every served token: the reference runs once over each
    prompt with its served tokens, and each served token is read at the
    position that produced it.  With ``control``, a pair: those gaps and
    the gaps of the tokens that the float8 control (``mode="fp8"``) puts
    first at the same positions."""
    seqs = [np.concatenate([p, g[:-1]]) for p, g in zip(prompts, generated)]
    rows = batch_rows(seqs)
    ref = forward(d, seed, rows, tiles, "f32")
    picks = np.zeros(rows.shape, np.int32)
    at = []
    for i, (p, g) in enumerate(zip(prompts, generated)):
        picks[i, len(p) - 1:len(p) - 1 + len(g)] = g
        at.append((i, slice(len(p) - 1, len(p) - 1 + len(g))))

    def read(chosen):
        gaps = np.asarray(token_gaps(ref, chosen))
        return np.concatenate([gaps[i, s] for i, s in at])

    served = read(jnp.asarray(picks))
    if not control:
        return served
    return served, read(forward(d, seed, rows, tiles, "fp8").argmax(-1))
