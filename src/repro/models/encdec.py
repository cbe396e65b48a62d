"""Whisper-style encoder-decoder (the ``audio`` family).

The conv audio frontend is a stub per the assignment: ``input_specs``
provides precomputed frame embeddings (B, n_frames, d_model).  Positions
are sinusoidal on both sides (the real model's learned decoder positions
cap at 448; our assigned decode shapes go far beyond, so sinusoidal is the
faithful-in-spirit choice — noted in DESIGN.md).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel import shard
from .config import ModelConfig
from . import layers as L

Params = dict[str, Any]


def sinusoid(seq_len: int, d_model: int) -> jax.Array:
    pos = np.arange(seq_len)[:, None]
    dim = np.arange(d_model // 2)[None, :]
    inv = 1.0 / (10_000 ** (2 * dim / d_model))
    table = np.concatenate([np.sin(pos * inv), np.cos(pos * inv)], axis=-1)
    return jnp.asarray(table, dtype=jnp.float32)


def _init_cross(cfg: ModelConfig, key) -> Params:
    D, H, hd = cfg.d_model, cfg.n_heads, cfg.hd
    ks = list(jax.random.split(key, 4))
    dt = cfg.jnp_dtype
    return {
        "wq": L._dense_init(ks[0], (D, H * hd), dt),
        "wk": L._dense_init(ks[1], (D, H * hd), dt),
        "wv": L._dense_init(ks[2], (D, H * hd), dt),
        "wo": L._dense_init(ks[3], (H * hd, D), dt),
    }


def init_encdec(cfg: ModelConfig, key) -> Params:
    enc = cfg.encoder
    ks = list(jax.random.split(key, enc.n_layers + cfg.n_layers + 3))
    dt = cfg.jnp_dtype
    enc_layers = []
    for i in range(enc.n_layers):
        sub = list(jax.random.split(ks[i], 2))
        enc_layers.append({
            "ln1": jnp.zeros((cfg.d_model,), dt),
            "ln2": jnp.zeros((cfg.d_model,), dt),
            "attn": L.init_attention(cfg, sub[0]),
            "ffn": L.init_ffn(cfg, sub[1], gelu=True),
        })
    dec_layers = []
    for i in range(cfg.n_layers):
        sub = list(jax.random.split(ks[enc.n_layers + i], 3))
        dec_layers.append({
            "ln1": jnp.zeros((cfg.d_model,), dt),
            "ln_x": jnp.zeros((cfg.d_model,), dt),
            "ln2": jnp.zeros((cfg.d_model,), dt),
            "attn": L.init_attention(cfg, sub[0]),
            "cross": _init_cross(cfg, sub[1]),
            "ffn": L.init_ffn(cfg, sub[2], gelu=True),
        })
    return {
        "embed": L._dense_init(ks[-1], (cfg.vocab_size, cfg.d_model), dt, fan_in=cfg.d_model),
        "enc_layers": jax.tree.map(lambda *xs: jnp.stack(xs), *enc_layers),
        "dec_layers": jax.tree.map(lambda *xs: jnp.stack(xs), *dec_layers),
        "ln_enc": jnp.zeros((cfg.d_model,), dt),
        "ln_f": jnp.zeros((cfg.d_model,), dt),
        "lm_head": L._dense_init(ks[-2], (cfg.d_model, cfg.vocab_size), dt),
    }


def _bidir_attention(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    B, S, _ = x.shape
    q, k, v = L._qkv(cfg, p, x)
    pos = jnp.arange(S)
    big = jnp.full((S,), -1, jnp.int32)  # everything visible: use k_pos <= +inf
    out = L._masked_softmax_attn(q, k, v, jnp.full((S,), S, jnp.int32), pos,
                                 None)
    return L.linear(out.reshape(B, S, -1), p["wo"])


def _cross_attention(cfg: ModelConfig, p: Params, x, enc_k, enc_v) -> jax.Array:
    """x (B, Sq, D); enc_k/enc_v (B, Sk, H, hd) precomputed."""
    B, Sq, _ = x.shape
    H, hd = cfg.n_heads, cfg.hd
    q = L.linear(x, p["wq"]).reshape(B, Sq, H, hd)
    Sk = enc_k.shape[1]
    out = L._masked_softmax_attn(
        q, enc_k, enc_v, jnp.full((Sq,), Sk, jnp.int32), jnp.arange(Sk), None
    )
    return L.linear(out.reshape(B, Sq, -1), p["wo"])


def encode(cfg: ModelConfig, params: Params, frames: jax.Array,
           *, scan_unroll: bool = False) -> jax.Array:
    """frames (B, Tf, D) from the conv stub -> encoder output (B, Tf, D)."""
    x = frames.astype(cfg.jnp_dtype) + sinusoid(frames.shape[1], cfg.d_model).astype(cfg.jnp_dtype)
    x = shard(x, "batch", None, None)

    def body(x, lp):
        h = L.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        x = x + _bidir_attention(cfg, lp["attn"], h)
        h = L.rmsnorm(x, lp["ln2"], cfg.norm_eps)
        x = x + L.ffn(cfg, lp["ffn"], h)
        return shard(x, "batch", None, None), None

    x, _ = jax.lax.scan(body, x, params["enc_layers"],
                        unroll=True if scan_unroll else 1)
    return L.rmsnorm(x, params["ln_enc"], cfg.norm_eps)


def forward_encdec(
    cfg: ModelConfig,
    params: Params,
    batch: dict[str, jax.Array],
    *,
    lut=None,
    backend: str = "auto",
    remat: str = "none",
    scan_unroll: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Teacher-forced: encode frames, decode tokens.  Returns (logits, 0)."""
    enc_out = encode(cfg, params, batch["frames"], scan_unroll=scan_unroll)
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = params["embed"][tokens].astype(cfg.jnp_dtype)
    x = x + sinusoid(S, cfg.d_model).astype(cfg.jnp_dtype)
    x = shard(x, "batch", None, None)
    H, hd = cfg.n_heads, cfg.hd

    def body(x, lp):
        h = L.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = L._qkv(cfg, lp["attn"], h)
        pos = jnp.arange(S)
        x = x + L.linear(
            L._masked_softmax_attn(q, k, v, pos, pos, None).reshape(B, S, -1),
            lp["attn"]["wo"],
        )
        h = L.rmsnorm(x, lp["ln_x"], cfg.norm_eps)
        enc_k = L.linear(enc_out, lp["cross"]["wk"]).reshape(B, -1, H, hd)
        enc_v = L.linear(enc_out, lp["cross"]["wv"]).reshape(B, -1, H, hd)
        x = x + _cross_attention(cfg, lp["cross"], h, enc_k, enc_v)
        h = L.rmsnorm(x, lp["ln2"], cfg.norm_eps)
        x = x + L.ffn(cfg, lp["ffn"], h)
        return shard(x, "batch", None, None), None

    if remat != "none":
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["dec_layers"],
                        unroll=True if scan_unroll else 1)
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"]).astype(jnp.float32)
    return logits, jnp.float32(0.0)


def encdec_loss(cfg, params, batch, *, lut=None, backend="auto", remat="none",
                scan_unroll=False):
    logits, _ = forward_encdec(cfg, params, batch, backend=backend,
                               remat=remat, scan_unroll=scan_unroll)
    tokens = batch["tokens"]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return nll.mean()


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def init_encdec_caches(cfg: ModelConfig, batch: int, seq_len: int) -> list[Params]:
    """Self-attn KV per decoder layer + precomputed cross KV slots."""
    enc = cfg.encoder
    dt = cfg.jnp_dtype
    caches = []
    for _ in range(cfg.n_layers):
        caches.append({
            "k": jnp.zeros((batch, seq_len, cfg.n_kv_heads, cfg.hd), dt),
            "v": jnp.zeros((batch, seq_len, cfg.n_kv_heads, cfg.hd), dt),
            "xk": jnp.zeros((batch, enc.n_frames, cfg.n_heads, cfg.hd), dt),
            "xv": jnp.zeros((batch, enc.n_frames, cfg.n_heads, cfg.hd), dt),
        })
    return caches


def prefill_cross(cfg: ModelConfig, params: Params, frames: jax.Array,
                  caches: list[Params]) -> list[Params]:
    """Encode once and stage each decoder layer's cross K/V into its cache."""
    enc_out = encode(cfg, params, frames)
    B = frames.shape[0]
    H, hd = cfg.n_heads, cfg.hd
    out = []
    for i, c in enumerate(caches):
        lp = jax.tree.map(lambda a, i=i: a[i], params["dec_layers"])
        nc = dict(c)
        nc["xk"] = L.linear(enc_out, lp["cross"]["wk"]).reshape(B, -1, H, hd)
        nc["xv"] = L.linear(enc_out, lp["cross"]["wv"]).reshape(B, -1, H, hd)
        out.append(nc)
    return out


def decode_step_encdec(
    cfg: ModelConfig,
    params: Params,
    caches: list[Params],
    tokens: jax.Array,  # (B, 1)
    pos: jax.Array,     # ()
) -> tuple[jax.Array, list[Params]]:
    B = tokens.shape[0]
    x = params["embed"][tokens].astype(cfg.jnp_dtype)
    x = x + jax.lax.dynamic_slice_in_dim(
        sinusoid(caches[0]["k"].shape[1], cfg.d_model), pos, 1
    ).astype(cfg.jnp_dtype)[None]
    new_caches = []
    for i, cache in enumerate(caches):
        lp = jax.tree.map(lambda a, i=i: a[i], params["dec_layers"])
        h = L.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        attn_out, upd = L.attention_decode(cfg, lp["attn"], h, cache, pos, None)
        x = x + attn_out
        h = L.rmsnorm(x, lp["ln_x"], cfg.norm_eps)
        x = x + _cross_attention(cfg, lp["cross"], h, cache["xk"], cache["xv"])
        h = L.rmsnorm(x, lp["ln2"], cfg.norm_eps)
        x = x + L.ffn(cfg, lp["ffn"], h)
        nc = dict(cache)
        nc.update(upd)
        new_caches.append(nc)
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"]).astype(jnp.float32)[:, 0]
    return logits, new_caches
