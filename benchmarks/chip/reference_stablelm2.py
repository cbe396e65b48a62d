"""The plain reference for StableLM-2: a float32 ``jax.numpy`` forward of
the published block, with the MLP's matmuls through the plan's LUT
products.

It imports nothing of the program.  It follows ``StableLmForCausalLM``
as its ``config.json`` sets it up: LayerNorm with gain ``1 + g`` and a
bias before attention, before the MLP and at the end; q/k/v projections
with a bias; half-split rotary over the leading ``rotary_dims`` of each
head, the rest unrotated; causal attention; attention and the MLP each on
its own residual; a SiLU-gated MLP whose three matmuls run W``b``A``b``
through the layer's product table (:func:`benchmarks.chip.reference.
lut_linear`); an untied head.  Weights are rebuilt from the seed layer by
layer (:mod:`benchmarks.chip.weights_stablelm2`).

``mode="f32"`` is the reference; ``mode="fp8"`` its control, every matmul
outside the LUT path on float8 (e4m3) operands, as in
:mod:`benchmarks.chip.reference`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .reference import batch_rows, einsum, lut_linear, token_gaps
from .weights import base_key, embed_weights, final_norm, head_weights
from .weights_stablelm2 import Dims, final_norm_bias, layer_weights


def layernorm(x, g, b, eps: float):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * (1.0 + g) + b


def rope(x, theta: float, rot: int):
    """Half-split rotary over dims ``[0, rot)`` at positions 0..S-1;
    x (B, S, H, hd)."""
    half = rot // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, x[..., rot:]],
                           axis=-1)


def block(d: Dims, p: dict, x, tile, mode: str):
    """One decoder layer over whole sequences, x (B, S, D) f32."""
    B, S, D = x.shape
    H, Hkv, hd = d.heads, d.kv_heads, d.head_dim
    a = p["attn"]
    h = layernorm(x, p["ln1"], p["ln1_b"], d.norm_eps)
    q = (einsum("bsd,df->bsf", h, a["wq"], mode) + a["bq"]).reshape(
        B, S, H, hd)
    k = (einsum("bsd,df->bsf", h, a["wk"], mode) + a["bk"]).reshape(
        B, S, Hkv, hd)
    v = (einsum("bsd,df->bsf", h, a["wv"], mode) + a["bv"]).reshape(
        B, S, Hkv, hd)
    q, k = rope(q, d.rope_theta, d.rotary_dims), rope(k, d.rope_theta,
                                                      d.rotary_dims)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    scores = einsum("bqhd,bkhd->bhqk", q, k, mode) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ctx = einsum("bhqk,bkhd->bqhd", probs, v, mode).reshape(B, S, H * hd)
    x = x + einsum("bsf,fd->bsd", ctx, a["wo"], mode)

    f = p["ffn"]
    h = layernorm(x, p["ln2"], p["ln2_b"], d.norm_eps).reshape(B * S, D)
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
        layernorm(x, final_norm(d, b).astype(jnp.float32),
                  final_norm_bias(d, b).astype(jnp.float32), d.norm_eps),
        head_weights(d, b).astype(jnp.float32), mode))
    return head(base, x)


def served_gaps(d: Dims, seed: int, tiles: np.ndarray, prompts, generated,
                control: bool = False):
    """Gaps of every served token, as
    :func:`benchmarks.chip.reference.served_gaps` reads them, over this
    block's forward."""
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
