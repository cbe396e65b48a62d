"""Decoder-only language model: init / forward / decode for every family.

Design notes (DESIGN.md §5, §7):

* **Scan-over-layers** for train/prefill: per-layer params are stacked on a
  leading axis and the block body is traced once — HLO size is O(1) in
  depth, which matters both for the 1-core CPU here and for real compile
  times at 1000+ nodes.  Per-layer heterogeneity (gemma3 local/global,
  hymba's periodic global layers) rides through the scan as a traced
  per-layer window scalar (``-1`` = global).
* **Python loop over layers** for decode: caches are *heterogeneous*
  (ring buffers for sliding-window layers, full-length for global layers,
  recurrent states for SSM/RWKV), so each layer owns its own cache pytree
  and the loop unrolls — decode graphs are small.
* MoE aux (load-balance) losses accumulate through the scan carry.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel import shard
from ..quant.int4 import prepare_weight
from .config import ModelConfig
from . import layers as L

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# per-layer window schedule
# ---------------------------------------------------------------------------
def window_schedule(cfg: ModelConfig) -> np.ndarray | int | None:
    """None = all-global; int = uniform window; array (L,) = per-layer
    (-1 marks a global layer)."""
    if cfg.local_global_every is not None:
        win = np.full((cfg.n_layers,), cfg.local_window, dtype=np.int32)
        win[cfg.local_global_every - 1 :: cfg.local_global_every] = -1
        return win
    if cfg.sliding_window is not None:
        return int(cfg.sliding_window)
    return None


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _init_layer(cfg: ModelConfig, key) -> Params:
    ks = list(jax.random.split(key, 4))
    dt = cfg.jnp_dtype
    p: Params = {"ln1": jnp.zeros((cfg.d_model,), dt), "ln2": jnp.zeros((cfg.d_model,), dt)}
    if cfg.norm == "layer":
        p["ln1_b"] = jnp.zeros((cfg.d_model,), dt)
        p["ln2_b"] = jnp.zeros((cfg.d_model,), dt)
    if cfg.rwkv is not None:
        p["rwkv"] = L.init_rwkv(cfg, ks[0])
        return p
    if cfg.mla is not None:
        p["attn"] = L.init_mla(cfg, ks[0])
    else:
        p["attn"] = L.init_attention(cfg, ks[0])
    if cfg.ssm is not None:
        p["ssm"] = L.init_ssm(cfg, ks[1])
    if cfg.moe is not None:
        p["moe"] = L.init_moe(cfg, ks[2])
    else:
        p["ffn"] = L.init_ffn(cfg, ks[3])
    return p


def init_lm(cfg: ModelConfig, key) -> Params:
    ks = list(jax.random.split(key, cfg.n_layers + 3))
    dt = cfg.jnp_dtype
    # one batched init over the layer keys: the same values as a loop over
    # layers, but a jitted full-width init stays one layer's program
    stacked = jax.vmap(lambda k: _init_layer(cfg, k))(
        jnp.stack(ks[:cfg.n_layers]))
    params: Params = {
        "embed": L._dense_init(ks[-1], (cfg.vocab_size, cfg.d_model), dt, fan_in=cfg.d_model),
        "layers": stacked,
        "ln_f": jnp.zeros((cfg.d_model,), dt),
    }
    if cfg.norm == "layer":
        params["ln_f_b"] = jnp.zeros((cfg.d_model,), dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = L._dense_init(ks[-2], (cfg.d_model, cfg.vocab_size), dt)
    if cfg.vision is not None:
        params["vis_proj"] = L._dense_init(
            ks[-3], (cfg.vision.d_vision, cfg.d_model), dt
        )
    return params


# ---------------------------------------------------------------------------
# one transformer block (full-sequence mode)
# ---------------------------------------------------------------------------
def _block_full(cfg: ModelConfig, lp: Params, x, window, lut, backend):
    if cfg.rwkv is not None:
        h, _ = L.rwkv_time_mix(cfg, lp["rwkv"], L.rmsnorm(x, lp["ln1"], cfg.norm_eps))
        x = x + h
        h, _ = L.rwkv_channel_mix(cfg, lp["rwkv"], L.rmsnorm(x, lp["ln2"], cfg.norm_eps))
        return x + h, jnp.float32(0.0)

    h = L.block_norm(cfg, lp, "ln1", x)
    if cfg.mla is not None:
        attn_out = L.mla_attention_full(cfg, lp["attn"], h)
    else:
        attn_out = L.attention_full(cfg, lp["attn"], h, window, backend=backend)
    if cfg.ssm is not None:  # hybrid: parallel SSM head fused with attention
        ssm_out, _ = L.ssm_mix(cfg, lp["ssm"], h)
        attn_out = 0.5 * (attn_out + ssm_out)
    x = x + attn_out

    h = L.block_norm(cfg, lp, "ln2", x)
    if cfg.moe is not None:
        mlp_out, aux = L.moe_ffn(cfg, lp["moe"], h, lut)
    else:
        mlp_out, aux = L.ffn(cfg, lp["ffn"], h, lut), jnp.float32(0.0)
    return x + mlp_out, aux


def forward_lm(
    cfg: ModelConfig,
    params: Params,
    batch: dict[str, jax.Array],
    *,
    lut: jax.Array | None = None,
    backend: str = "auto",
    remat: str = "none",
    scan_unroll: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Teacher-forced forward.  Returns (logits (B, S_total, V), aux_loss).

    ``batch['tokens']``: (B, S) int32.  VLM batches add ``'patches'``
    (B, P, d_vision) which are projected and prepended.
    ``lut``: optional approximate-multiplier table — either one
    (side, side) table shared by every layer, or a per-layer
    (n_layers, side, side) stack (a QoS
    :class:`~repro.library.qos.LayerPlan`), which rides through the layer
    scan alongside the stacked params; side = 16 (W4A4) or 256 (W8A8).
    ``scan_unroll``: unroll the layer scan, so that XLA cost_analysis
    (which counts a rolled scan body once) sees every layer.
    """
    tokens = batch["tokens"]
    x = params["embed"][tokens].astype(cfg.jnp_dtype)
    if cfg.vision is not None:
        pv = jnp.einsum("bpd,dm->bpm", batch["patches"].astype(cfg.jnp_dtype),
                        params["vis_proj"])
        x = jnp.concatenate([pv, x], axis=1)
    x = shard(x, "batch", None, None)

    win = window_schedule(cfg)
    lut_ = lut if cfg.approx_mlp else None
    per_layer_lut = lut_ is not None and jnp.ndim(lut_) == 3

    def body(carry, scanned):
        x, aux = carry
        lp = scanned["lp"]
        w = scanned["win"] if isinstance(win, np.ndarray) else win
        l = scanned["lut"] if per_layer_lut else lut_
        x, aux_i = _block_full(cfg, lp, x, w, l, backend)
        x = shard(x, "batch", None, None)
        return (x, aux + aux_i), None

    if remat != "none":
        policy = (
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            if remat == "dots"
            else jax.checkpoint_policies.nothing_saveable
        )
        body = jax.checkpoint(body, policy=policy)

    xs: dict = {"lp": params["layers"]}
    if isinstance(win, np.ndarray):
        xs["win"] = jnp.asarray(win)
    if per_layer_lut:
        xs["lut"] = jnp.asarray(lut_)
    (x, aux), _ = jax.lax.scan(
        body, (x, jnp.float32(0.0)), xs, unroll=True if scan_unroll else 1
    )

    x = L.block_norm(cfg, params, "ln_f", x)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head).astype(jnp.float32)
    logits = shard(logits, "batch", None, "model")
    return logits, aux


def lm_loss(cfg, params, batch, *, lut=None, backend="auto", remat="none",
            scan_unroll=False):
    """Next-token cross-entropy (text positions only for VLM)."""
    logits, aux = forward_lm(cfg, params, batch, lut=lut, backend=backend,
                             remat=remat, scan_unroll=scan_unroll)
    tokens = batch["tokens"]
    n_prefix = cfg.vision.n_patches if cfg.vision is not None else 0
    logits_text = logits[:, n_prefix:, :]
    pred = logits_text[:, :-1]
    tgt = tokens[:, 1:]
    logp = jax.nn.log_softmax(pred, axis=-1)
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    return nll.mean() + aux


# ---------------------------------------------------------------------------
# decode: heterogeneous per-layer caches, Python loop over layers
# ---------------------------------------------------------------------------
def init_decode_caches(cfg: ModelConfig, batch: int, seq_len: int) -> list[Params]:
    """One cache pytree per layer, sized by that layer's attention kind."""
    win = window_schedule(cfg)
    dt = cfg.jnp_dtype
    caches: list[Params] = []
    for layer in range(cfg.n_layers):
        c: Params = {}
        if cfg.rwkv is not None:
            rw = cfg.rwkv
            H = cfg.d_model // rw.head_dim
            c["x_tm"] = jnp.zeros((batch, 1, cfg.d_model), dt)
            c["x_cm"] = jnp.zeros((batch, 1, cfg.d_model), dt)
            c["wkv"] = jnp.zeros((batch, H, rw.head_dim, rw.head_dim), jnp.float32)
            caches.append(c)
            continue
        if cfg.mla is not None:
            mla = cfg.mla
            c["ckv"] = jnp.zeros((batch, seq_len, mla.kv_lora_rank), dt)
            c["kr"] = jnp.zeros((batch, seq_len, mla.qk_rope_head_dim), dt)
        else:
            if isinstance(win, np.ndarray):
                w = int(win[layer])
                slots = seq_len if w < 0 else min(w, seq_len)
            elif isinstance(win, int):
                slots = min(win, seq_len)
            else:
                slots = seq_len
            c["k"] = jnp.zeros((batch, slots, cfg.n_kv_heads, cfg.hd), dt)
            c["v"] = jnp.zeros((batch, slots, cfg.n_kv_heads, cfg.hd), dt)
        if cfg.ssm is not None:
            sm = cfg.ssm
            di = sm.d_inner or cfg.d_model
            c["ssm"] = jnp.zeros((batch, di, sm.state_dim), jnp.float32)
        caches.append(c)
    return caches


def init_paged_caches(cfg: ModelConfig, batch: int, n_pages: int,
                      page_size: int, max_len: int) -> list[Params]:
    """Decode caches for continuous batching: global-attention layers
    share one ``(n_pages + 1, page_size, ...)`` page *pool* (physical
    page 0 is the allocator's scratch page), sliding-window layers keep a
    small per-slot ring (their cache is already bounded by the window —
    paging it would buy nothing), and ``max_len`` bounds the per-request
    page-table width.  Recurrent state (RWKV / SSM) cannot be paged or
    resumed from KV alone, so those families are rejected here rather
    than silently served wrong."""
    if cfg.rwkv is not None or cfg.ssm is not None:
        raise ValueError(
            f"{cfg.name}: continuous batching pages KV caches; recurrent "
            f"state (rwkv/ssm) has no positional cache to page — use the "
            f"fixed-batch engine for this family")
    win = window_schedule(cfg)
    dt = cfg.jnp_dtype
    caches: list[Params] = []
    for layer in range(cfg.n_layers):
        c: Params = {}
        if cfg.mla is not None:
            mla = cfg.mla
            c["ckvp"] = jnp.zeros((n_pages + 1, page_size,
                                   mla.kv_lora_rank), dt)
            c["krp"] = jnp.zeros((n_pages + 1, page_size,
                                  mla.qk_rope_head_dim), dt)
        else:
            w = (int(win[layer]) if isinstance(win, np.ndarray)
                 else win if isinstance(win, int) else -1)
            if w is not None and w > 0:
                slots = min(w, max_len)
                c["k"] = jnp.zeros((batch, slots, cfg.n_kv_heads, cfg.hd), dt)
                c["v"] = jnp.zeros((batch, slots, cfg.n_kv_heads, cfg.hd), dt)
            else:
                c["kp"] = jnp.zeros((n_pages + 1, page_size,
                                     cfg.n_kv_heads, cfg.hd), dt)
                c["vp"] = jnp.zeros((n_pages + 1, page_size,
                                     cfg.n_kv_heads, cfg.hd), dt)
        caches.append(c)
    # on the active mesh from the start: the step's outputs carry the mesh
    # in their type, so unplaced first inputs would trace the step twice
    return [{name: shard(x, *(None,) * x.ndim) for name, x in c.items()}
            for c in caches]


def shard_decode_caches(caches: list[Params], cfg: ModelConfig) -> list[Params]:
    """Apply logical sharding to caches: batch over data when divisible,
    else context-parallel over the cache-sequence axis (long_500k, B=1)."""
    out = []
    for c in caches:
        sc = dict(c)
        for name in ("k", "v"):
            if name in sc:
                sc[name] = shard(sc[name], "batch", "cache_seq", "model", None)
        if "ckv" in sc:
            sc["ckv"] = shard(sc["ckv"], "batch", "cache_seq", "model")
            sc["kr"] = shard(sc["kr"], "batch", "cache_seq", None)
        if "ssm" in sc:
            sc["ssm"] = shard(sc["ssm"], "batch", "model", None)
        if "wkv" in sc:
            sc["wkv"] = shard(sc["wkv"], "batch", "model", None, None)
        out.append(sc)
    return out


def _block_decode(cfg: ModelConfig, lp: Params, x, cache: Params, pos, window,
                  lut=None):
    new_cache = dict(cache)
    if cfg.rwkv is not None:
        h = L.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        h_in = jnp.concatenate([cache["x_tm"], h], axis=1)  # token-shift via state
        out, (x_tm, wkv) = L.rwkv_time_mix(
            cfg, lp["rwkv"], h, state=(cache["x_tm"], cache["wkv"])
        )
        x = x + out
        h = L.rmsnorm(x, lp["ln2"], cfg.norm_eps)
        out, x_cm = L.rwkv_channel_mix(cfg, lp["rwkv"], h, x_last=cache["x_cm"])
        new_cache.update(x_tm=x_tm, wkv=wkv, x_cm=x_cm)
        return x + out, new_cache

    h = L.block_norm(cfg, lp, "ln1", x)
    if cfg.mla is not None:
        attn_out, upd = L.mla_attention_decode(cfg, lp["attn"], h, cache, pos)
    else:
        attn_out, upd = L.attention_decode(cfg, lp["attn"], h, cache, pos, window)
    new_cache.update(upd)
    if cfg.ssm is not None:
        ssm_out, s = L.ssm_mix(cfg, lp["ssm"], h, state=cache["ssm"])
        new_cache["ssm"] = s
        attn_out = 0.5 * (attn_out + ssm_out)
    x = x + attn_out

    h = L.block_norm(cfg, lp, "ln2", x)
    if cfg.moe is not None:
        mlp_out, _ = L.moe_ffn(cfg, lp["moe"], h, lut, dropless=True)
    else:
        mlp_out = L.ffn(cfg, lp["ffn"], h, lut)
    return x + mlp_out, new_cache


def prepare_mlp_weights(params: Params, widths: int | tuple[int, ...]):
    """Every dense MLP matrix of ``params`` prepared for the LUT matmul
    (:func:`repro.quant.int4.prepare_weight`), laid out as the LUT stack
    a decode step takes beside it: for one width ``bits``,
    ``{name: PreparedWeight}`` stacked ``(L, K, N)`` per matrix; for a
    per-layer width map, ``{bits: {name: PreparedWeight}}`` stacked
    ``(n_group, K, N)`` in layer order.  One jitted call per stacked
    matrix, on the device.  A model without dense MLP layers (MoE, RWKV)
    gives ``{}``: its matrices stay per call."""
    ffn = params["layers"].get("ffn", {})
    if not ffn:
        return {}
    if isinstance(widths, int):
        return {name: jax.jit(prepare_weight, static_argnums=1)(w, widths)
                for name, w in ffn.items()}
    groups = {}
    for b in sorted(set(widths)):
        rows = np.array([i for i, wb in enumerate(widths) if wb == b])
        groups[b] = {name: jax.jit(lambda w, rows=rows, b=b:
                                   prepare_weight(w[rows], b))(w)
                     for name, w in ffn.items()}
    return groups


def _decode_layers(cfg: ModelConfig, params: Params, caches: list[Params],
                   tokens, luts, width_map, block, prepared=None):
    """The layer loop under both decode steps: embed ``tokens``, run
    ``block(lp, x, cache, window=, lut=)`` on each layer, then the head.
    ``prepared`` (:func:`prepare_mlp_weights`, laid out as ``luts``)
    stands in for each layer's float MLP matrices."""
    win = window_schedule(cfg)
    luts_ = luts if cfg.approx_mlp else None
    if any(isinstance(v, np.ndarray)
           for v in jax.tree.leaves((luts_, prepared))):
        # a host numpy table would be traced as a compile-time constant and
        # every plan swap would silently rebuild the executable
        raise TypeError(
            "decode step luts and prepared weights must be jax arrays "
            "passed as jit arguments, not numpy constants (serving hot-swap "
            "relies on this)"
        )
    group_pos: list[int] | None = None
    if isinstance(luts_, dict):
        if width_map is None or len(width_map) != cfg.n_layers:
            raise ValueError(
                f"a mixed-width luts dict needs a width_map with one entry "
                f"per layer (got {width_map!r} for {cfg.n_layers} layers)"
            )
        # layer i's row within its width group = how many earlier layers
        # share its width (group stacks are packed in layer order)
        group_pos = [width_map[:i].count(width_map[i])
                     for i in range(cfg.n_layers)]

    def row(stack, i):
        """Layer ``i``'s entry of a per-layer stack (or pytree of them),
        or of its width group's in a mixed-width dict."""
        if group_pos is not None:
            return jax.tree.map(lambda a: a[group_pos[i]],
                                stack[width_map[i]])
        return jax.tree.map(lambda a: a[i], stack)

    # a table per layer (a stack, or a mixed-width dict), or one shared
    per_layer = group_pos is not None or (luts_ is not None
                                          and jnp.ndim(luts_) == 3)
    x = params["embed"][tokens].astype(cfg.jnp_dtype)
    x = shard(x, "batch", None, None)
    new_caches: list[Params] = []
    layer_params = [
        jax.tree.map(lambda a, i=i: a[i], params["layers"])
        for i in range(cfg.n_layers)
    ]
    for i, (lp, cache) in enumerate(zip(layer_params, caches)):
        if isinstance(win, np.ndarray):
            w = int(win[i])
            w = None if w < 0 else w
        else:
            w = win
        lut_i = row(luts_, i) if per_layer else luts_
        if lut_i is not None and prepared:
            lp = {**lp, "ffn": {**lp["ffn"], **row(prepared, i)}}
        x, nc = block(lp, x, cache, window=w, lut=lut_i)
        new_caches.append(nc)
    with jax.named_scope("head"):
        x = L.block_norm(cfg, params, "ln_f", x)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("bsd,dv->bsv", x, head).astype(jnp.float32)[:, 0]
    return logits, new_caches


def decode_step(
    cfg: ModelConfig,
    params: Params,
    caches: list[Params],
    tokens: jax.Array,   # (B, 1) int32 — the newest token
    pos: jax.Array,      # () int32 — its absolute position
    *,
    luts: jax.Array | dict[int, jax.Array] | None = None,
    #     (L, side, side) per-layer LUTs, (side, side) shared, or a
    #     mixed-width dict {bits: (n_group, side, side)} — side = 16
    #     (W4A4) or 256 (composed W8A8 tables)
    width_map: tuple[int, ...] | None = None,
    prepared=None,
) -> tuple[jax.Array, list[Params]]:
    """One serving step: append token at ``pos``, return next-token logits.

    ``luts``: optional approximate-multiplier tables routing each layer's
    MLP matmuls (QoS plan); the decode loop is unrolled per layer, so the
    per-layer table is just indexed out.  The table side picks the
    operand width (``repro.quant.approx_linear`` infers bias and code
    range from it), so the same decode step serves W4A4 ``(L, 16, 16)``
    and W8A8 ``(L, 256, 256)`` stacks — at a *fixed* width per trace:
    shapes are jit-static, so width moves recompile while same-width plan
    swaps never do.

    Mixed-width serving passes ``luts`` as a dict holding one stack per
    width group plus a static ``width_map`` (one entry per layer): layer
    ``i`` reads table ``luts[width_map[i]]`` at its position within its
    group (layer order within the group).  The width map is part of the
    traced python structure, so it is frozen per trace — same-map plan
    swaps re-stack the group arrays and reuse the one executable, exactly
    like the single-width case.

    ``luts`` must ride through ``jax.jit`` as a *real argument* (a jax
    array / tracer pytree), never a closed-over host constant: the
    adaptive serving runtime (:mod:`repro.serving`) hot-swaps plans
    between batches by passing a different stack to the same traced
    executable, which only works if tracing never baked the table in.

    ``prepared``: optional :func:`prepare_mlp_weights` of ``params`` at
    the stack's widths, laid out as ``luts`` and passed the same way, so
    the step quantizes only the activations; without it each MLP matmul
    quantizes its weight on every call, to the same result.
    """
    return _decode_layers(cfg, params, caches, tokens, luts, width_map,
                          partial(_block_decode, cfg, pos=pos), prepared)


# ---------------------------------------------------------------------------
# paged decode: per-slot positions, shared page pools (continuous batching)
# ---------------------------------------------------------------------------
def _block_decode_paged(cfg: ModelConfig, lp: Params, x, cache: Params,
                        pos, tables, active, window, lut=None):
    new_cache = dict(cache)
    h = L.block_norm(cfg, lp, "ln1", x)
    with jax.named_scope("attention"):
        if cfg.mla is not None:
            attn_out, upd = L.mla_attention_decode_paged(
                cfg, lp["attn"], h, cache, pos, tables, active)
        elif "kp" in cache:
            attn_out, upd = L.attention_decode_paged(
                cfg, lp["attn"], h, cache, pos, tables, active)
        else:
            attn_out, upd = L.attention_decode_ring(
                cfg, lp["attn"], h, cache, pos, active, window)
    new_cache.update(upd)
    x = x + attn_out

    h = L.block_norm(cfg, lp, "ln2", x)
    with jax.named_scope("mlp"):
        if cfg.moe is not None:
            mlp_out, _ = L.moe_ffn(cfg, lp["moe"], h, lut, dropless=True)
        else:
            mlp_out = L.ffn(cfg, lp["ffn"], h, lut)
    return x + mlp_out, new_cache


def decode_step_paged(
    cfg: ModelConfig,
    params: Params,
    caches: list[Params],
    tokens: jax.Array,   # (B, 1) int32 — each slot's newest token
    pos: jax.Array,      # (B,) int32 — per-slot absolute positions
    active: jax.Array,   # (B,) bool — which slots hold a live request
    tables: jax.Array,   # (B, T) int32 — per-slot physical page tables
    *,
    luts: jax.Array | dict[int, jax.Array] | None = None,
    width_map: tuple[int, ...] | None = None,
    prepared=None,
) -> tuple[jax.Array, list[Params]]:
    """One continuous-batching step: every *active* slot advances one
    token at its own position; inactive slots compute padding rows whose
    cache writes land on the scratch page (page 0) and whose logits the
    host discards.

    This is :func:`decode_step` with the batch-shared scalar ``pos``
    replaced by per-slot vectors and the dense global-attention caches
    replaced by page pools (:func:`init_paged_caches`); both run one layer
    loop, so the LUT-stack and ``prepared`` contracts are the same, and all
    shapes are fixed by ``(max_slots, pages_per_slot, page_size)``, so
    requests joining and leaving the running batch never retrace.

    Named scopes label the step's operations for a profiler trace:
    ``attention`` (projections, the paged KV write and gather, the output
    projection), ``mlp`` (with ``quantize`` inside
    :func:`repro.quant.approx_linear`: the activation side and the
    correction, and the weight side too where no ``prepared`` is given)
    and ``head`` (final norm, logits)."""
    return _decode_layers(cfg, params, caches, tokens, luts, width_map,
                          partial(_block_decode_paged, cfg, pos=pos,
                                  tables=tables, active=active), prepared)
