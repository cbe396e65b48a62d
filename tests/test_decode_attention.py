"""Per-row decode attention against a float64 reference.

``_decode_attn_rows`` contracts the query heads of each KV head as one group
against K and V as gathered (bf16), with an f32 softmax and the
probabilities entering PV as three bf16 terms.  Every output must equal
the float64 reference, computed from the same bf16 inputs and rounded to
bf16, within one bf16 ulp: at MHA (StableLM-2's 32/32 heads of 64) and at
GQA (Qwen3's 32/8 heads of 128), and through the sliding-window ring.
"""

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro.models import layers as L
from repro.models.config import ModelConfig

BF16 = ml_dtypes.bfloat16


def _reference(q, k, v, mask):
    """float64 attention; query head ``h`` reads KV head ``h // G``."""
    q, k, v = (np.asarray(jnp.asarray(x, jnp.float32), np.float64)
               for x in (q, k, v))
    G = q.shape[2] // k.shape[2]
    k, v = np.repeat(k, G, axis=2), np.repeat(v, G, axis=2)
    logits = np.einsum("bhd,bkhd->bhk", q[:, 0], k) / np.sqrt(q.shape[-1])
    logits = np.where(np.asarray(mask)[:, None, :], logits, -np.inf)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhk,bkhd->bhd", p, v)[:, None]


def _assert_within_one_bf16_ulp(out, ref):
    want = ref.astype(BF16)
    mag = np.abs(want)
    ulp = ((mag.view(np.uint16) + 1).view(BF16).astype(np.float64)
           - mag.astype(np.float64))
    err = np.abs(np.asarray(out, np.float64) - want.astype(np.float64))
    worst = np.unravel_index(np.argmax(err / ulp), err.shape)
    assert (err <= ulp).all(), (
        f"{int((err > ulp).sum())} of {err.size} outputs off by more than "
        f"one bf16 ulp; worst at {worst}: {float(err[worst])} > "
        f"{float(ulp[worst])}")


@pytest.mark.parametrize("H,Hkv,hd", [(32, 32, 64), (32, 8, 128)],
                         ids=["mha-32x64", "gqa-32-8x128"])
def test_decode_attn_rows_matches_f64_reference(H, Hkv, hd):
    """Rows of lengths 16, 1, 7 and 14 keys, and an inactive row that
    attends to slot 0 alone, as the paged step points it."""
    B, K = 5, 16
    rng = np.random.default_rng(H * 1000 + Hkv * 10 + hd)
    q, k, v = (jnp.asarray(rng.normal(0.0, s, shape), jnp.bfloat16)
               for s, shape in ((1.5, (B, 1, H, hd)), (1.5, (B, K, Hkv, hd)),
                                (1.0, (B, K, Hkv, hd))))
    pos = np.array([15, 0, 6, 13, 9])
    active = np.array([True, True, True, True, False])
    idx = np.arange(K)[None]
    mask = np.where(active[:, None], idx <= pos[:, None], idx == 0)
    out = L._decode_attn_rows(q, k, v, jnp.asarray(mask))
    assert out.dtype == jnp.bfloat16 and out.shape == (B, 1, H, hd)
    _assert_within_one_bf16_ulp(out, _reference(q, k, v, mask))


def test_decode_ring_matches_f64_reference():
    """The sliding-window ring (window 8) at GQA 8/2: a row before its
    first wrap, a row long past it, a row at position 0 whose ring holds a
    freed occupant's stale KV, and an inactive row.  The output projection
    is the identity, so the attention output is compared as it leaves."""
    B, C, H, Hkv, hd = 4, 8, 8, 2, 64
    D = H * hd
    cfg = ModelConfig(name="ring", family="dense", n_layers=1, d_model=D,
                      n_heads=H, n_kv_heads=Hkv, d_ff=4 * D, vocab_size=16,
                      head_dim=hd, sliding_window=C)
    rng = np.random.default_rng(8)
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)            # noqa: E731
    p = {"wq": bf(rng.normal(0, 1.5 / np.sqrt(D), (D, H * hd))),
         "wk": bf(rng.normal(0, 1.5 / np.sqrt(D), (D, Hkv * hd))),
         "wv": bf(rng.normal(0, 1 / np.sqrt(D), (D, Hkv * hd))),
         "wo": bf(np.eye(D))}
    x = bf(rng.normal(size=(B, 1, D)))
    cache = {n: bf(rng.normal(size=(B, C, Hkv, hd))) for n in ("k", "v")}
    pos = np.array([3, 21, 0, 5], np.int32)
    active = np.array([True, True, True, False])

    out, new = L.attention_decode_ring(cfg, p, x, cache, jnp.asarray(pos),
                                       jnp.asarray(active), C)

    # the new token's q, k, v as the layer computes them (eager, op by op)
    q, k_new, v_new = L._qkv(cfg, p, x)
    q = L._rows_rope(q, jnp.asarray(pos), cfg.rotary_dims, cfg.rope_theta)
    k_new = L._rows_rope(k_new, jnp.asarray(pos), cfg.rotary_dims,
                         cfg.rope_theta)
    k, v = np.array(cache["k"]), np.array(cache["v"])
    rows = np.arange(B)
    k[rows, pos % C] = np.asarray(k_new[:, 0])
    v[rows, pos % C] = np.asarray(v_new[:, 0])
    np.testing.assert_array_equal(np.asarray(new["k"]), k)
    np.testing.assert_array_equal(np.asarray(new["v"]), v)
    # ring slot i holds the newest position <= pos congruent to i mod C
    held = pos[:, None] - (pos[:, None] - np.arange(C)[None]) % C
    mask = np.where(active[:, None], held >= 0, np.arange(C)[None] == 0)
    ref = _reference(q, jnp.asarray(k), jnp.asarray(v), mask)
    _assert_within_one_bf16_ulp(out, ref.reshape(B, 1, D))
