"""StableLM-2's sizes and seeded weights, made by the benchmark and not
the program.

What differs from :mod:`benchmarks.chip.weights` is the block: LayerNorm
biases beside each norm's gain, q/k/v biases, and the share of each head
that rotary turns.  The embedding, the head and the final norm's gain are
that module's, drawn from the same seed positions.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .weights import (_gain, _matrix, base_key, embed_weights, final_norm,
                      head_weights)

FINAL_NORM_BIAS = (1 << 20) + 3


@dataclass(frozen=True)
class Dims:
    """StableLM-2's sizes, read from a configuration file."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    rotary_dims: int
    norm_eps: float
    tied: bool
    lut_bits: int
    bias_scale: float

    @classmethod
    def from_doc(cls, doc: dict) -> "Dims":
        D, H = int(doc["hidden_size"]), int(doc["num_attention_heads"])
        hd = D // H
        return cls(layers=int(doc["num_hidden_layers"]), d_model=D, heads=H,
                   kv_heads=int(doc["num_key_value_heads"]), head_dim=hd,
                   d_ff=int(doc["intermediate_size"]),
                   vocab=int(doc["vocab_size"]),
                   rope_theta=float(doc["rope_theta"]),
                   rotary_dims=int(hd * float(doc["partial_rotary_factor"])),
                   norm_eps=float(doc["layer_norm_eps"]),
                   tied=bool(doc["tie_word_embeddings"]),
                   lut_bits=int(doc["approx_bits"]),
                   bias_scale=float(doc["init"]["bias_scale"]))


def _bias(key, n, scale, dtype):
    return (scale * jax.random.normal(key, (n,), jnp.float32)).astype(dtype)


def layer_weights(d: Dims, key, dtype=jnp.bfloat16) -> dict:
    """One decoder layer, in ``repro.models.layers``' parameter names."""
    k = jax.random.split(key, 10)
    D, H, Hkv, hd, F = d.d_model, d.heads, d.kv_heads, d.head_dim, d.d_ff
    attn = {"wq": _matrix(k[0], (D, H * hd), dtype),
            "wk": _matrix(k[1], (D, Hkv * hd), dtype),
            "wv": _matrix(k[2], (D, Hkv * hd), dtype),
            "wo": _matrix(k[3], (H * hd, D), dtype),
            "bq": _bias(k[4], H * hd, d.bias_scale, dtype),
            "bk": _bias(k[5], Hkv * hd, d.bias_scale, dtype),
            "bv": _bias(k[6], Hkv * hd, d.bias_scale, dtype)}
    ffn = {"w1": _matrix(k[7], (D, F), dtype),
           "w3": _matrix(k[8], (D, F), dtype),
           "w2": _matrix(k[9], (F, D), dtype)}
    kn = jax.random.split(jax.random.fold_in(key, 99), 4)
    return {"ln1": _gain(kn[0], D, dtype), "ln2": _gain(kn[1], D, dtype),
            "ln1_b": _bias(kn[2], D, d.bias_scale, dtype),
            "ln2_b": _bias(kn[3], D, d.bias_scale, dtype),
            "attn": attn, "ffn": ffn}


def final_norm_bias(d: Dims, base, dtype=jnp.bfloat16) -> jax.Array:
    return _bias(jax.random.fold_in(base, FINAL_NORM_BIAS), d.d_model,
                 d.bias_scale, dtype)


def make_params(d: Dims, seed: int) -> dict:
    """Every served parameter, bf16 on the default device, in one call."""

    def build(base):
        keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(
            jnp.arange(d.layers))
        params = {"embed": embed_weights(d, base),
                  "layers": jax.vmap(lambda k: layer_weights(d, k))(keys),
                  "ln_f": final_norm(d, base),
                  "ln_f_b": final_norm_bias(d, base)}
        if not d.tied:
            params["lm_head"] = head_weights(d, base)
        return params

    return jax.jit(build)(base_key(seed))
