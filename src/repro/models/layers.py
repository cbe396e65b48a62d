"""Layer primitives for the architecture zoo.

Everything is a pure function over explicit parameter pytrees (no module
framework): ``init_*`` builds params, the apply functions take
``(cfg, params, activations, ...)``.  Two execution modes share each
mixer: full-sequence (train / prefill) and single-step (decode, with an
explicit cache/state).  Sharding is annotated with *logical* axes via
:func:`repro.parallel.shard` — a no-op outside a mesh context.

Attention dispatch: the einsum path is the reference and supports a
*traced* window size (needed for gemma3's per-layer local/global pattern
inside one ``lax.scan``); the Pallas flash kernel is used on TPU for
uniform-window/causal layers.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from ..parallel import axis_extent, shard
from ..quant.int4 import approx_linear
from .config import ModelConfig

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------
def _dense_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[0]
    scale = 1.0 / np.sqrt(max(1, fan_in))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _keys(key, n):
    return list(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# norms / rope / linear
# ---------------------------------------------------------------------------
def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * (1.0 + w)


def layernorm(x: jax.Array, w: jax.Array, b: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    """LayerNorm with gain ``1 + w`` (the repo's gain convention) and a
    bias ``b``."""
    xf = x.astype(jnp.float32)
    xc = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    return (xc * jax.lax.rsqrt(var + eps)).astype(x.dtype) * (1.0 + w) + b


def block_norm(cfg: ModelConfig, p: Params, name: str,
               x: jax.Array) -> jax.Array:
    """The model's norm ``p[name]`` (``ln1``, ``ln2``, ``ln_f``): RMSNorm,
    or with ``cfg.norm == "layer"`` LayerNorm with the bias
    ``p[name + "_b"]``."""
    if cfg.norm == "layer":
        return layernorm(x, p[name], p[name + "_b"], cfg.norm_eps)
    return rmsnorm(x, p[name], cfg.norm_eps)


def linear(x: jax.Array, w, lut: jax.Array | None = None) -> jax.Array:
    """Matmul, optionally routed through the approximate-multiplier LUT;
    with a LUT, ``w`` may be a :class:`~repro.quant.int4.PreparedWeight`."""
    if lut is not None:
        return approx_linear(x, w, lut)
    return jnp.einsum("...d,df->...f", x, w)


def rope_tables(positions: jax.Array, rot_dims: int, theta: float):
    """positions (...,) -> cos/sin tables (..., rot_dims//2) for rotary
    over ``rot_dims`` dims (the head, or its leading share)."""
    half = rot_dims // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (..., S, H, hd); cos/sin (..., S, rot//2) — half-split rotation
    of the leading ``rot`` dims; dims ``rot:`` pass through (partial
    rotary, where the tables are narrower than the head)."""
    rot = 2 * cos.shape[-1]
    half = rot // 2
    x1, x2 = x[..., :half], x[..., half:rot]
    c = cos[..., None, :]  # broadcast over heads
    s = sin[..., None, :]
    parts = [x1 * c - x2 * s, x2 * c + x1 * s]
    if rot < x.shape[-1]:
        parts.append(x[..., rot:])
    out = jnp.concatenate(parts, axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention (einsum reference path; flash kernel on TPU)
# ---------------------------------------------------------------------------
def init_attention(cfg: ModelConfig, key) -> Params:
    D, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = cfg.jnp_dtype
    ks = _keys(key, 4)
    p = {
        "wq": _dense_init(ks[0], (D, H * hd), dt),
        "wk": _dense_init(ks[1], (D, Hkv * hd), dt),
        "wv": _dense_init(ks[2], (D, Hkv * hd), dt),
        "wo": _dense_init(ks[3], (H * hd, D), dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dt)
        p["k_norm"] = jnp.zeros((hd,), dt)
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dt)
        p["bk"] = jnp.zeros((Hkv * hd,), dt)
        p["bv"] = jnp.zeros((Hkv * hd,), dt)
    return p


def _qkv(cfg: ModelConfig, p: Params, x: jax.Array):
    B, S, _ = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd

    def proj(name, heads):
        y = linear(x, p["w" + name])
        if cfg.qkv_bias:
            y = y + p["b" + name]
        return y.reshape(B, S, heads, hd)

    q, k, v = proj("q", H), proj("k", Hkv), proj("v", Hkv)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def _masked_softmax_attn(q, k, v, q_pos, k_pos, window, k_valid=None):
    """Flat-head einsum attention with causal + (traced) window masking.

    q (B, Sq, H, hd); k/v (B, Sk, Hkv, hd); q_pos (Sq,), k_pos (Sk,).
    ``window``: None, a Python int, or a traced scalar (-1 == global).

    GQA is handled by *repeating* KV up to H heads: the flat H axis shards
    cleanly over the 16-way ``model`` mesh axis (96/16 etc.), whereas a
    grouped (Hkv, rep) layout with Hkv=8 < 16 forces XLA to replicate the
    S^2 score tensor on every device (observed 10x HBM inflation).  The
    repeat itself is free under sharding: each device materializes only
    its own heads' copies.
    """
    B, Sq, H, hd = q.shape
    out_dtype = q.dtype
    Hkv = k.shape[2]
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    k = shard(k, "batch", None, "model", None)
    v = shard(v, "batch", None, "model", None)
    scale = 1.0 / np.sqrt(hd)
    q, k = q.astype(jnp.float32), k.astype(jnp.float32)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = shard(logits, "batch", "model", None, None)
    mask = k_pos[None, :] <= q_pos[:, None]  # causal
    if window is not None:
        w = jnp.asarray(window)
        in_window = k_pos[None, :] > q_pos[:, None] - w
        mask = mask & jnp.where(w > 0, in_window, True)
    if k_valid is not None:
        mask = mask & k_valid[None, :]
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    v = v.astype(jnp.float32)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                     preferred_element_type=jnp.float32)
    out = shard(out, "batch", None, "model", None)
    # v's head dim may differ from q's (MLA: qk 192 vs v 128)
    return out.astype(out_dtype)


def attention_full(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,            # (B, S, D)
    window,                  # None | int | traced scalar (-1 = global)
    *,
    backend: str = "auto",
) -> jax.Array:
    B, S, _ = x.shape
    q, k, v = _qkv(cfg, p, x)
    pos = jnp.arange(S)
    cos, sin = rope_tables(pos, cfg.rotary_dims, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    q = shard(q, "batch", None, "model", None)
    k = shard(k, "batch", None, "model", None)
    use_flash = (
        backend in ("pallas", "pallas_interpret")
        or (backend == "auto" and jax.default_backend() == "tpu")
    ) and (window is None or isinstance(window, int))
    if use_flash:
        out = ops.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            causal=True, window=window, backend=backend,
        ).transpose(0, 2, 1, 3)
    else:
        out = _masked_softmax_attn(q, k, v, pos, pos, window)
    out = shard(out, "batch", None, "model", None)
    return linear(out.reshape(B, S, -1), p["wo"])


def attention_decode(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,            # (B, 1, D)
    cache: dict[str, jax.Array],   # {"k","v"}: (B, C, Hkv, hd); C = cache slots
    pos: jax.Array,          # () int32 — absolute position of the new token
    window,                  # None | int — ring-buffer window if set
) -> tuple[jax.Array, dict[str, jax.Array]]:
    B = x.shape[0]
    q, k_new, v_new = _qkv(cfg, p, x)
    cos, sin = rope_tables(pos[None], cfg.rotary_dims, cfg.rope_theta)
    q = apply_rope(q, cos[None], sin[None])
    k_new = apply_rope(k_new, cos[None], sin[None])

    C = cache["k"].shape[1]
    slot = jnp.where(window is None, pos, pos % C) if window is not None else pos
    k = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, slot, 0, 0))
    new_cache = {"k": k, "v": v}

    if window is not None:
        # ring buffer: slot i holds absolute position with (pos - C, pos]
        idx = jnp.arange(C)
        k_pos = jnp.where(idx <= slot, pos - slot + idx, pos - slot - C + idx)
        k_valid = (k_pos >= 0) & (k_pos > pos - C - 1)
    else:
        idx = jnp.arange(C)
        k_pos = idx
        k_valid = idx <= pos
    out = _masked_softmax_attn(q, k, v, pos[None], k_pos, None, k_valid)
    out = linear(out.reshape(B, 1, -1), p["wo"])
    return out, new_cache


# ---------------------------------------------------------------------------
# MLA attention (DeepSeek): compressed KV cache + absorbed decode
# ---------------------------------------------------------------------------
def init_mla(cfg: ModelConfig, key) -> Params:
    mla = cfg.mla
    D, H = cfg.d_model, cfg.n_heads
    qk = mla.qk_nope_head_dim + mla.qk_rope_head_dim
    dt = cfg.jnp_dtype
    ks = _keys(key, 5)
    return {
        "wq": _dense_init(ks[0], (D, H * qk), dt),
        "wdkv": _dense_init(ks[1], (D, mla.kv_lora_rank + mla.qk_rope_head_dim), dt),
        "wuk": _dense_init(ks[2], (mla.kv_lora_rank, H * mla.qk_nope_head_dim), dt),
        "wuv": _dense_init(ks[3], (mla.kv_lora_rank, H * mla.v_head_dim), dt),
        "wo": _dense_init(ks[4], (H * mla.v_head_dim, D), dt),
    }


def mla_attention_full(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    mla = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    nope, rope_d, vd, R = (
        mla.qk_nope_head_dim, mla.qk_rope_head_dim, mla.v_head_dim, mla.kv_lora_rank
    )
    q = linear(x, p["wq"]).reshape(B, S, H, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    ckv_kr = linear(x, p["wdkv"])
    c_kv, k_rope = ckv_kr[..., :R], ckv_kr[..., R:]          # (B,S,R), (B,S,rope_d)
    k_nope = linear(c_kv, p["wuk"]).reshape(B, S, H, nope)
    v = linear(c_kv, p["wuv"]).reshape(B, S, H, vd)

    pos = jnp.arange(S)
    cos, sin = rope_tables(pos, rope_d, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)      # single shared head
    k_rope = jnp.broadcast_to(k_rope, (B, S, H, rope_d))

    qfull = jnp.concatenate([q_nope, q_rope], axis=-1)
    kfull = jnp.concatenate([k_nope, k_rope], axis=-1)
    out = _masked_softmax_attn(qfull, kfull, v, pos, pos, None)
    return linear(out.reshape(B, S, -1), p["wo"])


def mla_attention_decode(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,                 # (B, 1, D)
    cache: dict[str, jax.Array],  # {"ckv": (B, C, R), "kr": (B, C, rope_d)}
    pos: jax.Array,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Absorbed-matrix MLA decode: attention runs directly over the
    compressed cache; ``wuk`` folds into the query, ``wuv`` into the output
    (DeepSeek-V2's serving trick — the cache stays R + rope_d wide)."""
    mla = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    nope, rope_d, vd, R = (
        mla.qk_nope_head_dim, mla.qk_rope_head_dim, mla.v_head_dim, mla.kv_lora_rank
    )
    q = linear(x, p["wq"]).reshape(B, 1, H, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    cos, sin = rope_tables(pos[None], rope_d, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos[None], sin[None])

    ckv_kr = linear(x, p["wdkv"])
    c_new, kr_new = ckv_kr[..., :R], ckv_kr[..., R:]
    kr_new = apply_rope(kr_new[:, :, None, :], cos[None], sin[None])[:, :, 0]
    ckv = jax.lax.dynamic_update_slice(cache["ckv"], c_new, (0, pos, 0))
    kr = jax.lax.dynamic_update_slice(cache["kr"], kr_new, (0, pos, 0))
    new_cache = {"ckv": ckv, "kr": kr}

    # absorb wuk into q: q'[b,h,r] = sum_n q_nope[b,h,n] wuk[r, h*n]
    wuk = p["wuk"].reshape(R, H, nope)
    q_abs = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0].astype(jnp.float32),
                       wuk.astype(jnp.float32))             # (B, H, R)
    scale = 1.0 / np.sqrt(nope + rope_d)
    logits = (
        jnp.einsum("bhr,bcr->bhc", q_abs, ckv.astype(jnp.float32))
        + jnp.einsum("bhd,bcd->bhc", q_rope[:, 0].astype(jnp.float32),
                     kr.astype(jnp.float32))
    ) * scale
    C = ckv.shape[1]
    valid = jnp.arange(C) <= pos
    logits = jnp.where(valid[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bhc,bcr->bhr", probs, ckv.astype(jnp.float32))  # (B,H,R)
    wuv = p["wuv"].reshape(R, H, vd)
    out = jnp.einsum("bhr,rhv->bhv", ctx, wuv.astype(jnp.float32))
    out = out.reshape(B, 1, H * vd).astype(x.dtype)
    return linear(out, p["wo"]), new_cache


# ---------------------------------------------------------------------------
# paged / per-row decode attention (continuous batching)
#
# The fixed-batch decode path above shares one scalar ``pos`` across the
# whole batch.  Continuous batching mixes requests at *different* sequence
# positions in one step, so these variants take ``pos`` as a (B,) vector
# plus an ``active`` (B,) mask; global-attention KV lives in a shared page
# pool indexed by per-slot page tables (repro.serving.kvcache) instead of
# a dense per-slot cache.  Everything stays pure jnp gather/scatter —
# shapes are fixed by (max_slots, pages_per_slot, page_size), so the
# serving engine's single-trace contract survives joins and leaves.
# ---------------------------------------------------------------------------
def _decode_attn_rows(q, k, v, mask):
    """Single-token attention with a per-row key mask.

    q (B, 1, H, hd); k/v (B, K, Hkv, hd); mask (B, K) bool — True where
    row b may attend to key slot j.  The caller guarantees every row has
    at least one True (inactive rows point at one masked-garbage slot so
    the softmax never sees an all ``-inf`` row).

    Query head ``h·G + g`` (``G = H // Hkv``; 1 for MHA) reads KV head
    ``h``, so the G query heads of a group are contracted together against
    K and V as gathered: nothing repeats them to H heads or widens them.
    QKᵀ multiplies in the operands' dtype into f32, exact for bf16; mask
    and softmax are f32.  The probabilities enter PV split into three bf16
    terms stacked in one contraction: together they hold all 24 bits of
    each f32 probability, and each product with V's bf16 values is exact in
    f32, so the result is the f32 PV's up to accumulation order.
    """
    B, _, H, hd = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    k = shard(k, "batch", None, "model", None)
    v = shard(v, "batch", None, "model", None)
    qg = q.reshape(B, Hkv, G, hd)
    # a zero row keeps QKᵀ a matrix product at G = 1: XLA rewrites a
    # one-row contraction as an f32 multiply-reduce over a widened copy of K
    qg = jnp.concatenate([qg, jnp.zeros_like(qg[:, :, :1])], axis=2)
    logits = jnp.einsum("bhgd,bkhd->bhgk", qg, k,
                        preferred_element_type=jnp.float32)[:, :, :G]
    logits = logits / np.sqrt(hd)
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    terms = []
    for _ in range(3):
        terms.append(probs.astype(v.dtype))
        probs = probs - terms[-1].astype(jnp.float32)
    pv = jnp.einsum("bhgk,bkhd->bhgd", jnp.concatenate(terms, axis=2), v,
                    preferred_element_type=jnp.float32)
    out = (pv[:, :, :G] + pv[:, :, G:2 * G] + pv[:, :, 2 * G:])
    out = out.reshape(B, 1, H, hd)
    out = shard(out, "batch", None, "model", None)
    return out.astype(q.dtype)


def _rows_rope(x, pos, rot_dims, theta):
    """Per-row rope for single-token decode: x (B, 1, H, hd), pos (B,);
    rotary over the leading ``rot_dims`` dims of each head."""
    cos, sin = rope_tables(pos, rot_dims, theta)      # (B, rot_dims//2)
    return apply_rope(x, cos[:, None], sin[:, None])


def attention_decode_ring(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,                  # (B, 1, D)
    cache: dict[str, jax.Array],   # {"k","v"}: (B, C, Hkv, hd) per-slot ring
    pos: jax.Array,                # (B,) int32 — per-slot absolute position
    active: jax.Array,             # (B,) bool
    window: int,                   # ring capacity == attention window
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Sliding-window decode with *per-row* positions.  The ring layout
    is unchanged from :func:`attention_decode` (slot ``pos % C`` holds the
    newest token); only the position arithmetic became row-wise.  A slot
    whose occupant just joined at ``pos=0`` masks out every stale ring
    entry the previous occupant left behind — validity is derived from
    ``pos``, never from what the buffer happens to contain."""
    B = x.shape[0]
    q, k_new, v_new = _qkv(cfg, p, x)
    q = _rows_rope(q, pos, cfg.rotary_dims, cfg.rope_theta)
    k_new = _rows_rope(k_new, pos, cfg.rotary_dims, cfg.rope_theta)

    C = cache["k"].shape[1]
    slot = pos % C
    rows = jnp.arange(B)
    k = cache["k"].at[rows, slot].set(k_new[:, 0])
    v = cache["v"].at[rows, slot].set(v_new[:, 0])
    new_cache = {"k": k, "v": v}

    idx = jnp.arange(C)[None, :]                       # (1, C)
    pos_c, slot_c = pos[:, None], slot[:, None]
    # ring slot i holds absolute position in (pos - C, pos]
    k_pos = jnp.where(idx <= slot_c, pos_c - slot_c + idx,
                      pos_c - slot_c - C + idx)
    mask = (k_pos >= 0) & (k_pos > pos_c - C - 1)
    # inactive rows attend to exactly slot 0 (output discarded, but the
    # softmax must not see an empty row)
    mask = jnp.where(active[:, None], mask, idx == 0)
    out = _decode_attn_rows(q, k, v, mask)
    out = linear(out.reshape(B, 1, -1), p["wo"])
    return out, new_cache


def _paged_write(pool: jax.Array, new_row: jax.Array, pos: jax.Array,
                 tables: jax.Array) -> jax.Array:
    """Scatter one new per-slot row into the shared page pool.

    pool (P+1, page_size, ...); new_row (B, ...); pos (B,); tables
    (B, T) physical page ids.  Inactive slots carry all-scratch tables
    and ``pos=0``, so their writes land on the reserved scratch page —
    duplicate scratch writes race benignly (nobody reads it unmasked).
    """
    page_size = pool.shape[1]
    page = jnp.take_along_axis(tables, (pos // page_size)[:, None],
                               axis=1)[:, 0]
    return pool.at[page, pos % page_size].set(new_row)


def _paged_read(pool: jax.Array, tables: jax.Array) -> jax.Array:
    """Gather each slot's pages back into a contiguous per-slot view:
    pool (P+1, page_size, ...) + tables (B, T) -> (B, T*page_size, ...)."""
    B, T = tables.shape
    v = pool[tables]                                  # (B, T, page_size, ...)
    return v.reshape((B, T * pool.shape[1]) + pool.shape[2:])


def attention_decode_paged(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,                  # (B, 1, D)
    cache: dict[str, jax.Array],   # {"kp","vp"}: (P+1, page_size, Hkv, hd)
    pos: jax.Array,                # (B,) int32
    tables: jax.Array,             # (B, T) int32 physical page ids
    active: jax.Array,             # (B,) bool
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Global-attention decode over the shared page pool.  Each slot
    writes its new KV at ``table[pos // page_size], pos % page_size`` and
    attends over the gathered view of its own pages; positions a request
    has not written yet (stale KV from a freed request included) are
    masked by ``j <= pos``, so page *reuse* needs no zeroing."""
    B = x.shape[0]
    q, k_new, v_new = _qkv(cfg, p, x)
    q = _rows_rope(q, pos, cfg.rotary_dims, cfg.rope_theta)
    k_new = _rows_rope(k_new, pos, cfg.rotary_dims, cfg.rope_theta)

    kp = _paged_write(cache["kp"], k_new[:, 0], pos, tables)
    vp = _paged_write(cache["vp"], v_new[:, 0], pos, tables)
    new_cache = {"kp": kp, "vp": vp}

    k = _paged_read(kp, tables)                       # (B, K, Hkv, hd)
    v = _paged_read(vp, tables)
    idx = jnp.arange(k.shape[1])[None, :]             # logical positions
    mask = idx <= pos[:, None]
    mask = jnp.where(active[:, None], mask, idx == 0)
    out = _decode_attn_rows(q, k, v, mask)
    out = linear(out.reshape(B, 1, -1), p["wo"])
    return out, new_cache


def mla_attention_decode_paged(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,                  # (B, 1, D)
    cache: dict[str, jax.Array],   # {"ckvp": (P+1, S, R), "krp": (P+1, S, rd)}
    pos: jax.Array,                # (B,) int32
    tables: jax.Array,             # (B, T) int32
    active: jax.Array,             # (B,) bool
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Absorbed-matrix MLA decode over a paged compressed cache — the
    same serving trick as :func:`mla_attention_decode`, with the
    ``(B, C, R)`` dense cache replaced by a shared page pool."""
    mla = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    nope, rope_d, vd, R = (
        mla.qk_nope_head_dim, mla.qk_rope_head_dim, mla.v_head_dim,
        mla.kv_lora_rank,
    )
    q = linear(x, p["wq"]).reshape(B, 1, H, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    cos, sin = rope_tables(pos, rope_d, cfg.rope_theta)   # (B, rd//2)
    q_rope = apply_rope(q_rope, cos[:, None], sin[:, None])

    ckv_kr = linear(x, p["wdkv"])
    c_new, kr_new = ckv_kr[..., :R], ckv_kr[..., R:]
    kr_new = apply_rope(kr_new[:, :, None, :], cos[:, None],
                        sin[:, None])[:, :, 0]
    ckvp = _paged_write(cache["ckvp"], c_new[:, 0], pos, tables)
    krp = _paged_write(cache["krp"], kr_new[:, 0], pos, tables)
    new_cache = {"ckvp": ckvp, "krp": krp}

    ckv = _paged_read(ckvp, tables)                   # (B, K, R)
    kr = _paged_read(krp, tables)                     # (B, K, rope_d)
    wuk = p["wuk"].reshape(R, H, nope)
    q_abs = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0].astype(jnp.float32),
                       wuk.astype(jnp.float32))
    scale = 1.0 / np.sqrt(nope + rope_d)
    logits = (
        jnp.einsum("bhr,bcr->bhc", q_abs, ckv.astype(jnp.float32))
        + jnp.einsum("bhd,bcd->bhc", q_rope[:, 0].astype(jnp.float32),
                     kr.astype(jnp.float32))
    ) * scale
    idx = jnp.arange(ckv.shape[1])[None, :]
    mask = idx <= pos[:, None]
    mask = jnp.where(active[:, None], mask, idx == 0)
    logits = jnp.where(mask[:, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bhc,bcr->bhr", probs, ckv.astype(jnp.float32))
    wuv = p["wuv"].reshape(R, H, vd)
    out = jnp.einsum("bhr,rhv->bhv", ctx, wuv.astype(jnp.float32))
    out = out.reshape(B, 1, H * vd).astype(x.dtype)
    return linear(out, p["wo"]), new_cache


# ---------------------------------------------------------------------------
# dense FFN (SwiGLU / GELU)
# ---------------------------------------------------------------------------
def init_ffn(cfg: ModelConfig, key, *, gelu: bool = False, d_ff: int | None = None) -> Params:
    D, F = cfg.d_model, d_ff or cfg.d_ff
    dt = cfg.jnp_dtype
    ks = _keys(key, 3)
    if gelu:
        return {"w1": _dense_init(ks[0], (D, F), dt), "w2": _dense_init(ks[1], (F, D), dt)}
    return {
        "w1": _dense_init(ks[0], (D, F), dt),
        "w3": _dense_init(ks[1], (D, F), dt),
        "w2": _dense_init(ks[2], (F, D), dt),
    }


def ffn(cfg: ModelConfig, p: Params, x: jax.Array, lut=None) -> jax.Array:
    if "w3" in p:
        h = jax.nn.silu(linear(x, p["w1"], lut)) * linear(x, p["w3"], lut)
    else:
        h = jax.nn.gelu(linear(x, p["w1"], lut))
    h = shard(h, "batch", None, "model")
    return linear(h, p["w2"], lut)


# ---------------------------------------------------------------------------
# MoE FFN: sort-based dispatch into fixed-capacity expert blocks
# ---------------------------------------------------------------------------
def init_moe(cfg: ModelConfig, key) -> Params:
    mo = cfg.moe
    D, Fe, E = cfg.d_model, mo.d_ff_expert, mo.n_experts
    dt = cfg.jnp_dtype
    ks = _keys(key, 5)
    p = {
        "router": _dense_init(ks[0], (D, E), jnp.float32),
        "w1": _dense_init(ks[1], (E, D, Fe), dt, fan_in=D),
        "w3": _dense_init(ks[2], (E, D, Fe), dt, fan_in=D),
        "w2": _dense_init(ks[3], (E, Fe, D), dt, fan_in=Fe),
    }
    if mo.n_shared:
        sub = jax.random.split(ks[4], mo.n_shared)
        p["shared"] = [
            init_ffn(cfg, sub[i], d_ff=mo.d_ff_expert) for i in range(mo.n_shared)
        ]
    return p


def moe_ffn(cfg: ModelConfig, p: Params, x: jax.Array, lut=None,
            dropless: bool = False):
    """Returns (out, aux_loss).  Sort tokens by expert, pack each expert's
    tokens into a fixed-capacity block (megablocks-lite), run the expert
    stack as *batched matmuls* ``(E, C, D) x (E, D, F)``.  FLOPs =
    capacity_factor x active FLOPs in BOTH forward and backward, and the
    batched-matmul VJP partitions cleanly under GSPMD.  Overflow tokens
    beyond capacity are dropped (standard GShard/Switch semantics).
    """
    mo = cfg.moe
    B, S, D = x.shape
    T, K, E = B * S, mo.top_k, mo.n_experts
    flat = x.reshape(T, D)

    logits = linear(flat.astype(jnp.float32), p["router"])   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, K)                     # (T, K)
    gates = gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9)

    # load-balancing aux loss (Switch-style)
    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (T * K)
    aux = mo.aux_loss_weight * E * jnp.sum(me * ce)

    flat_e = idx.reshape(-1)                                 # (T*K,)
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_e)
    group_sizes = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)

    if dropless:
        # decode: per-step token counts are tiny and token dropping
        # would break decode == teacher-forced-forward; worst case all
        # tokens route to one expert -> capacity T*K is exact
        C = T * K
    else:
        C = max(1, int(np.ceil(T * K / E * mo.capacity_factor)))
    starts = jnp.cumsum(group_sizes) - group_sizes       # (E,)
    slot_c = jax.lax.broadcasted_iota(jnp.int32, (E, C), 1)
    src = starts[:, None] + slot_c                       # (E, C) into order
    valid = slot_c < group_sizes[:, None]
    src = jnp.minimum(src, T * K - 1)
    rows = flat_t[order][src]                            # (E, C) token ids
    g_blk = jnp.where(valid, flat_g[order][src], 0.0)    # (E, C)
    xs = flat[rows] * valid[..., None].astype(flat.dtype)  # (E, C, D)
    # expert parallelism when E divides the data axis (the classic MoE
    # all-to-all appears at the gather/scatter boundary); otherwise the
    # capacity axis stays data-parallel and expert weights stay FSDP
    ep = E % max(1, axis_extent("expert")) == 0 and axis_extent("expert") > 1
    if ep:
        xs = shard(xs, "expert", None, None)
    else:
        xs = shard(xs, None, "batch", None)
    h1 = jnp.einsum("ecd,edf->ecf", xs, p["w1"])
    h3 = jnp.einsum("ecd,edf->ecf", xs, p["w3"])
    hs = jax.nn.silu(h1) * h3
    hs = shard(hs, "expert" if ep else None, None if ep else "batch", "model")
    ys = jnp.einsum("ecf,efd->ecd", hs, p["w2"])         # (E, C, D)
    out = jnp.zeros((T, D), jnp.float32)
    out = out.at[rows.reshape(-1)].add(
        (ys * g_blk[..., None]).reshape(-1, D).astype(jnp.float32))

    out = out.reshape(B, S, D).astype(x.dtype)
    for sp in p.get("shared", []):
        out = out + ffn(cfg, sp, x, lut)
    return out, aux


# ---------------------------------------------------------------------------
# RWKV6 time-mix / channel-mix (Finch: data-dependent decay)
# ---------------------------------------------------------------------------
def init_rwkv(cfg: ModelConfig, key) -> Params:
    rw = cfg.rwkv
    D = cfg.d_model
    hd = rw.head_dim
    H = D // hd
    dt = cfg.jnp_dtype
    ks = _keys(key, 10)
    return {
        "mix_r": jnp.full((D,), 0.5, dt), "mix_k": jnp.full((D,), 0.5, dt),
        "mix_v": jnp.full((D,), 0.5, dt), "mix_g": jnp.full((D,), 0.5, dt),
        "mix_w": jnp.full((D,), 0.5, dt),
        "wr": _dense_init(ks[0], (D, D), dt), "wk": _dense_init(ks[1], (D, D), dt),
        "wv": _dense_init(ks[2], (D, D), dt), "wg": _dense_init(ks[3], (D, D), dt),
        "wo": _dense_init(ks[4], (D, D), dt),
        "w0": jnp.full((D,), -6.0, jnp.float32),
        "w_a": _dense_init(ks[5], (D, rw.decay_lora), dt),
        "w_b": _dense_init(ks[6], (rw.decay_lora, D), dt),
        "u": jnp.zeros((H, hd), jnp.float32),
        "ln_x": jnp.zeros((D,), dt),
        # channel mix
        "cmix_k": jnp.full((D,), 0.5, dt), "cmix_r": jnp.full((D,), 0.5, dt),
        "ck": _dense_init(ks[7], (D, cfg.d_ff), dt),
        "cv": _dense_init(ks[8], (cfg.d_ff, D), dt),
        "cr": _dense_init(ks[9], (D, D), dt),
    }


def _rwkv_wkv_scan(r, k, v, w, u, state0):
    """r/k/v (B,S,H,hd) f32; w (B,S,H,hd) decay in (0,1); u (H,hd).

    state (B,H,hd,hd):  y_t = r_t · (state + u⊙k_t ⊗ v_t);
                        state' = w_t⊙state + k_t ⊗ v_t  (⊙ along the k-index)
    """
    def step(state, inp):
        rt, kt, vt, wt = inp  # (B,H,hd) each
        kv = jnp.einsum("bhj,bhi->bhji", kt, vt)             # (B,H,hd,hd)
        y = jnp.einsum("bhj,bhji->bhi", rt, state + u[None, :, :, None] * kv)
        state = wt[..., None] * state + kv
        return state, y

    rs, ks_, vs, ws = (jnp.moveaxis(t, 1, 0) for t in (r, k, v, w))  # (S,B,H,hd)
    state, ys = jax.lax.scan(step, state0, (rs, ks_, vs, ws))
    return jnp.moveaxis(ys, 0, 1), state                      # (B,S,H,hd)


def rwkv_time_mix(cfg: ModelConfig, p: Params, x: jax.Array,
                  state: tuple | None = None):
    """Returns (out, (x_last, wkv_state)).  ``state=None`` => zeros (train);
    decode passes the carried state and S == 1."""
    rw = cfg.rwkv
    B, S, D = x.shape
    hd = rw.head_dim
    H = D // hd
    if state is None:
        x_prev_last = jnp.zeros((B, 1, D), x.dtype)
        wkv0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    else:
        x_prev_last, wkv0 = state
    xprev = jnp.concatenate([x_prev_last, x[:, :-1]], axis=1)

    def mixed(mu):
        return x + (xprev - x) * mu

    r = linear(mixed(p["mix_r"]), p["wr"]).reshape(B, S, H, hd).astype(jnp.float32)
    k = linear(mixed(p["mix_k"]), p["wk"]).reshape(B, S, H, hd).astype(jnp.float32)
    v = linear(mixed(p["mix_v"]), p["wv"]).reshape(B, S, H, hd).astype(jnp.float32)
    g = jax.nn.silu(linear(mixed(p["mix_g"]), p["wg"]))
    xw = mixed(p["mix_w"])
    dd = linear(jnp.tanh(linear(xw, p["w_a"])), p["w_b"]).astype(jnp.float32)
    w = jnp.exp(-jnp.exp(p["w0"][None, None] + dd))          # (B,S,D) in (0,1)
    w = w.reshape(B, S, H, hd)

    y, wkv = _rwkv_wkv_scan(r, k, v, w, p["u"], wkv0)
    y = rmsnorm(y.reshape(B, S, D).astype(x.dtype), p["ln_x"], cfg.norm_eps)
    out = linear(y * g, p["wo"])
    return out, (x[:, -1:], wkv)


def rwkv_channel_mix(cfg: ModelConfig, p: Params, x: jax.Array,
                     x_last: jax.Array | None = None):
    B, S, D = x.shape
    if x_last is None:
        x_last = jnp.zeros((B, 1, D), x.dtype)
    xprev = jnp.concatenate([x_last, x[:, :-1]], axis=1)
    xk = x + (xprev - x) * p["cmix_k"]
    xr = x + (xprev - x) * p["cmix_r"]
    k = jnp.square(jax.nn.relu(linear(xk, p["ck"])))
    out = jax.nn.sigmoid(linear(xr, p["cr"])) * linear(k, p["cv"])
    return out, x[:, -1:]


# ---------------------------------------------------------------------------
# SSM mixer (Mamba-style selective scan; hymba's parallel heads)
# ---------------------------------------------------------------------------
def init_ssm(cfg: ModelConfig, key) -> Params:
    sm = cfg.ssm
    D = cfg.d_model
    Di = sm.d_inner or D
    N = sm.state_dim
    dt = cfg.jnp_dtype
    ks = _keys(key, 6)
    return {
        "win": _dense_init(ks[0], (D, 2 * Di), dt),
        "wB": _dense_init(ks[1], (Di, N), dt),
        "wC": _dense_init(ks[2], (Di, N), dt),
        "wdt1": _dense_init(ks[3], (Di, sm.dt_rank), dt),
        "wdt2": _dense_init(ks[4], (sm.dt_rank, Di), dt),
        "A_log": jnp.zeros((Di, N), jnp.float32),
        "Dskip": jnp.ones((Di,), jnp.float32),
        "wout": _dense_init(ks[5], (Di, D), dt),
    }


def ssm_mix(cfg: ModelConfig, p: Params, x: jax.Array,
            state: jax.Array | None = None):
    """Selective scan.  Returns (out, state).  state (B, Di, N)."""
    sm = cfg.ssm
    B, S, D = x.shape
    Di = sm.d_inner or D
    N = sm.state_dim
    xz = linear(x, p["win"])
    xi, z = xz[..., :Di], xz[..., Di:]
    xi_f = xi.astype(jnp.float32)
    dt = jax.nn.softplus(linear(jnp.einsum("bsd,dr->bsr", xi, p["wdt1"]),
                                p["wdt2"]).astype(jnp.float32))   # (B,S,Di)
    Bt = linear(xi, p["wB"]).astype(jnp.float32)                   # (B,S,N)
    Ct = linear(xi, p["wC"]).astype(jnp.float32)                   # (B,S,N)
    A = -jnp.exp(p["A_log"])                                       # (Di,N)
    decay = jnp.exp(dt[..., None] * A[None, None])                 # (B,S,Di,N)
    contrib = (dt * xi_f)[..., None] * Bt[:, :, None, :]           # (B,S,Di,N)

    if state is None:
        state = jnp.zeros((B, Di, N), jnp.float32)

    def step(s, inp):
        d, c, ct = inp                                             # (B,Di,N)x2,(B,N)
        s = d * s + c
        y = jnp.einsum("bdn,bn->bd", s, ct)
        return s, y

    ds, cs, cts = (jnp.moveaxis(t, 1, 0) for t in (decay, contrib, Ct))
    state, ys = jax.lax.scan(step, state, (ds, cs, cts))
    y = jnp.moveaxis(ys, 0, 1) + p["Dskip"][None, None] * xi_f     # (B,S,Di)
    out = linear(y.astype(x.dtype) * jax.nn.silu(z), p["wout"])
    return out, state
