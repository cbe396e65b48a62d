"""Model sizes and seeded weights, made by the benchmark and not the program.

The served parameters are built on the device in one jitted call, in
bf16, in the layout ``repro.models.lm`` reads (stacked layers).  The
reference rebuilds any one layer from the same seed with
:func:`layer_weights`, so it never takes an array the program held.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .traffic import jax_seed

EMBED, HEAD, FINAL_NORM = 1 << 20, (1 << 20) + 1, (1 << 20) + 2


@dataclass(frozen=True)
class Dims:
    """A dense decoder's sizes, read from a configuration file."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qk_norm: bool
    rope_theta: float
    norm_eps: float
    tied: bool
    lut_bits: int
    mlp_out_scale: float = 1.0
    qk_gain: float = 1.0

    @classmethod
    def from_doc(cls, doc: dict) -> "Dims":
        return cls(layers=int(doc["num_hidden_layers"]),
                   d_model=int(doc["hidden_size"]),
                   heads=int(doc["num_attention_heads"]),
                   kv_heads=int(doc["num_key_value_heads"]),
                   head_dim=int(doc["head_dim"]),
                   d_ff=int(doc["intermediate_size"]),
                   vocab=int(doc["vocab_size"]),
                   qk_norm=bool(doc["qk_norm"]),
                   rope_theta=float(doc["rope_theta"]),
                   norm_eps=float(doc["rms_norm_eps"]),
                   tied=bool(doc["tie_word_embeddings"]),
                   lut_bits=int(doc["approx_bits"]),
                   mlp_out_scale=float(doc.get("init", {}).get(
                       "mlp_out_scale", 1.0)),
                   qk_gain=float(doc.get("init", {}).get("qk_gain", 1.0)))


def base_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(jax_seed(seed))


def _matrix(key, shape, dtype, fan_in=None, scale=1.0):
    fan_in = shape[0] if fan_in is None else fan_in
    w = jax.random.normal(key, shape, jnp.float32) * (scale / np.sqrt(fan_in))
    return w.astype(dtype)


def _gain(key, n, dtype, mean=1.0):
    # the program's norms scale by (1 + g)
    g = mean - 1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)
    return g.astype(dtype)


def layer_weights(d: Dims, key, dtype=jnp.bfloat16) -> dict:
    """One decoder layer, in ``repro.models.layers``' parameter names."""
    k = jax.random.split(key, 9)
    D, H, Hkv, hd, F = d.d_model, d.heads, d.kv_heads, d.head_dim, d.d_ff
    attn = {"wq": _matrix(k[0], (D, H * hd), dtype),
            "wk": _matrix(k[1], (D, Hkv * hd), dtype),
            "wv": _matrix(k[2], (D, Hkv * hd), dtype),
            "wo": _matrix(k[3], (H * hd, D), dtype)}
    if d.qk_norm:
        attn["q_norm"] = _gain(k[4], hd, dtype, d.qk_gain)
        attn["k_norm"] = _gain(k[5], hd, dtype, d.qk_gain)
    ffn = {"w1": _matrix(k[6], (D, F), dtype),
           "w3": _matrix(k[7], (D, F), dtype),
           "w2": _matrix(k[8], (F, D), dtype, scale=d.mlp_out_scale)}
    kn = jax.random.split(jax.random.fold_in(key, 99), 2)
    return {"ln1": _gain(kn[0], D, dtype), "ln2": _gain(kn[1], D, dtype),
            "attn": attn, "ffn": ffn}


def embed_weights(d: Dims, base, dtype=jnp.bfloat16) -> jax.Array:
    return _matrix(jax.random.fold_in(base, EMBED), (d.vocab, d.d_model),
                   dtype, fan_in=d.d_model)


def head_weights(d: Dims, base, dtype=jnp.bfloat16) -> jax.Array:
    """The output projection ``(d_model, vocab)``: the embedding's
    transpose when tied."""
    if d.tied:
        return embed_weights(d, base, dtype).T
    return _matrix(jax.random.fold_in(base, HEAD), (d.d_model, d.vocab),
                   dtype)


def final_norm(d: Dims, base, dtype=jnp.bfloat16) -> jax.Array:
    return _gain(jax.random.fold_in(base, FINAL_NORM), d.d_model, dtype)


def make_params(d: Dims, seed: int) -> dict:
    """Every served parameter, bf16 on the default device, in one call.
    The key is an argument, so every seed runs the one cached program."""

    def build(base):
        keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(
            jnp.arange(d.layers))
        params = {"embed": embed_weights(d, base),
                  "layers": jax.vmap(lambda k: layer_weights(d, k))(keys),
                  "ln_f": final_norm(d, base)}
        if not d.tied:
            params["lm_head"] = head_weights(d, base)
        return params

    return jax.jit(build)(base_key(seed))
