"""The plain reference of StableLM-2's published block, for the CPU tests.

A float32 ``jax.numpy`` forward over whole sequences under
``jax.default_matmul_precision("highest")``: no cache, no kernels, no
batching tricks.  It follows ``StableLmForCausalLM`` as its
``config.json`` sets it up:

- LayerNorm (gain and bias) before attention, before the MLP and at the
  end; the gain is stored as ``w`` and applied as ``1 + w``, the
  program's convention;
- q, k and v projections with a bias, o without;
- rotary over the leading ``rotary_dims`` of each head, half-split within
  them, the rest of the head unrotated;
- causal softmax attention, scaled by ``1 / sqrt(head_dim)``, keys and
  values repeated over the query heads they serve;
- attention and the MLP each on its own residual (not parallel);
- a SiLU-gated MLP without bias, and an untied head.

With a ``tile`` the MLP's three matmuls run W``bits``A``bits`` through
the product table it names, by the benchmark reference's exact integer
emulation (``benchmarks.chip.reference.lut_linear``).  It reads the
parameter tree of ``repro.models.lm.init_lm`` and the sizes of a
``ModelConfig``, and nothing else of the program.
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip.reference import lut_linear  # noqa: E402


def layernorm(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * (1.0 + w) + b


def rope(x, rot: int, theta: float):
    """Rotary at positions 0..S-1 over dims ``[0, rot)`` of x (S, H, hd)."""
    half = rot // 2
    freqs = 1.0 / theta ** (np.arange(half) / half)
    ang = np.arange(x.shape[0])[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, x[..., rot:]],
                           axis=-1)


def _mlp_matmul(h, w, tile, bits):
    if tile is None:
        return h @ w
    return lut_linear(h, w, jnp.asarray(tile, jnp.int32), bits)


def forward(cfg, params, tokens, tile=None, bits: int = 8):
    """Logits ``(S, vocab)`` f32 for one token row ``(S,)``."""
    f32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    S = len(tokens)
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    rot = int(hd * cfg.rotary_fraction)
    causal = np.tril(np.ones((S, S), bool))
    with jax.default_matmul_precision("highest"):
        x = f32["embed"][jnp.asarray(tokens)]
        for i in range(cfg.n_layers):
            p = jax.tree.map(lambda a: a[i], f32["layers"])
            a = p["attn"]
            h = layernorm(x, p["ln1"], p["ln1_b"], cfg.norm_eps)
            q = (h @ a["wq"] + a["bq"]).reshape(S, H, hd)
            k = (h @ a["wk"] + a["bk"]).reshape(S, Hkv, hd)
            v = (h @ a["wv"] + a["bv"]).reshape(S, Hkv, hd)
            q = rope(q, rot, cfg.rope_theta)
            k = jnp.repeat(rope(k, rot, cfg.rope_theta), H // Hkv, axis=1)
            v = jnp.repeat(v, H // Hkv, axis=1)
            scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
            probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
            ctx = jnp.einsum("hqk,khd->qhd", probs, v).reshape(S, H * hd)
            x = x + ctx @ a["wo"]
            f = p["ffn"]
            h = layernorm(x, p["ln2"], p["ln2_b"], cfg.norm_eps)
            up = _mlp_matmul(h, f["w1"], tile, bits)
            gate = _mlp_matmul(h, f["w3"], tile, bits)
            x = x + _mlp_matmul(jax.nn.silu(up) * gate, f["w2"], tile, bits)
        h = layernorm(x, f32["ln_f"], f32["ln_f_b"], cfg.norm_eps)
        return h @ f32["lm_head"]
