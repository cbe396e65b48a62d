"""Per-arch smoke tests (reduced configs): one forward/train step on CPU,
asserting output shapes and finiteness, plus decode-vs-forward consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import decode_fn, forward_fn, init_caches, init_model, loss_fn
from repro.models.config import SHAPES
from repro.train import OptimizerConfig, init_opt_state, make_train_step

B, S = 2, 16


def _batch(cfg, key, seq=S):
    batch = {"tokens": jax.random.randint(key, (B, seq), 0, cfg.vocab_size)}
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(key, (B, cfg.encoder.n_frames, cfg.d_model))
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            key, (B, cfg.vision.n_patches, cfg.vision.d_vision))
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finiteness(arch):
    cfg = get_config(arch, reduced=True)
    key = jax.random.PRNGKey(0)
    params = init_model(cfg, key)
    batch = _batch(cfg, key)
    logits, aux = forward_fn(cfg)(cfg, params, batch)
    prefix = cfg.vision.n_patches if cfg.vision is not None else 0
    assert logits.shape == (B, S + prefix, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "mixtral-8x7b", "rwkv6-3b",
                                  "hymba-1.5b", "whisper-tiny"])
def test_one_train_step_reduces_loss_direction(arch):
    """One AdamW step runs, produces finite metrics, and changes params."""
    cfg = get_config(arch, reduced=True)
    key = jax.random.PRNGKey(1)
    params = init_model(cfg, key)
    opt = init_opt_state(params)
    step = make_train_step(cfg, OptimizerConfig(lr=1e-3), remat="none")
    batch = _batch(cfg, key)
    new_params, new_opt, metrics = jax.jit(step)(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"])) and float(metrics["grad_norm"]) > 0
    assert int(new_opt["step"]) == 1
    diff = jax.tree.map(
        lambda a, b: float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()),
        params, new_params)
    assert max(jax.tree.leaves(diff)) > 0


def test_microbatch_accumulation_matches_full_batch():
    """Grad accumulation over 2 microbatches == single big batch (loss)."""
    cfg = get_config("stablelm-1.6b", reduced=True)
    key = jax.random.PRNGKey(2)
    params = init_model(cfg, key)
    opt = init_opt_state(params)
    batch = _batch(cfg, key)
    s1 = make_train_step(cfg, OptimizerConfig(), microbatches=1, remat="none")
    s2 = make_train_step(cfg, OptimizerConfig(), microbatches=2, remat="none")
    _, _, m1 = jax.jit(s1)(params, opt, batch)
    _, _, m2 = jax.jit(s2)(params, opt, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-2


@pytest.mark.parametrize("arch", [
    "qwen3-4b",
    "gemma3-1b",
    "deepseek-v2-lite-16b",
    "rwkv6-3b",
    "hymba-1.5b",
    "mixtral-8x7b",
])
def test_decode_matches_forward(arch):
    """Decoding token-by-token reproduces the teacher-forced logits.

    MoE: the equivalence only holds dropless — decode is dropless by
    design; raise the forward capacity factor so no token drops there
    either (capacity dropping is batch-dependent by construction)."""
    import dataclasses

    cfg = get_config(arch, reduced=True)
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=float(cfg.moe.n_experts)))
    key = jax.random.PRNGKey(3)
    params = init_model(cfg, key)
    seq = 8
    batch = _batch(cfg, key, seq=seq)
    full_logits, _ = forward_fn(cfg)(cfg, params, batch)
    prefix = cfg.vision.n_patches if cfg.vision is not None else 0

    caches = init_caches(cfg, B, seq)
    step = decode_fn(cfg)
    got = []
    for t in range(seq):
        logits, caches = step(cfg, params, caches, batch["tokens"][:, t:t+1],
                              jnp.int32(t))
        got.append(logits)
    got = jnp.stack(got, axis=1)  # (B, seq, V)
    want = full_logits[:, prefix:, :]
    err = float(jnp.abs(got - want).max())
    assert err < 8e-2, err  # bf16 roundoff across different contraction orders
    # random-init logits are near-flat, so argmax ties flip easily; require
    # agreement well above chance (1/vocab) to catch systematic divergence
    agree = float((jnp.argmax(got, -1) == jnp.argmax(want, -1)).mean())
    assert agree >= 0.6, agree


def test_scan_unroll_is_equivalent():
    cfg = get_config("qwen3-4b", reduced=True)
    key = jax.random.PRNGKey(4)
    params = init_model(cfg, key)
    batch = _batch(cfg, key)
    l1 = loss_fn(cfg)(cfg, params, batch)
    l2 = loss_fn(cfg)(cfg, params, batch, scan_unroll=True)
    assert abs(float(l1) - float(l2)) < 1e-4


def test_sliding_window_limits_attention():
    """A token further than the receptive field back cannot influence the
    output.  Uses dropless MoE capacity: capacity-dropping couples tokens
    through router competition (real GShard semantics), which would leak
    influence through a non-attention channel."""
    import dataclasses

    cfg = get_config("mixtral-8x7b", reduced=True)  # window 32, 2 layers
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=float(cfg.moe.n_experts)))
    key = jax.random.PRNGKey(5)
    params = init_model(cfg, key)
    seq = 80  # receptive field = n_layers * (window-1) = 62 < 79
    tok = jax.random.randint(key, (1, seq), 0, cfg.vocab_size)
    tok2 = tok.at[0, 0].set((tok[0, 0] + 1) % cfg.vocab_size)
    l1, _ = forward_fn(cfg)(cfg, params, {"tokens": tok})
    l2, _ = forward_fn(cfg)(cfg, params, {"tokens": tok2})
    assert float(jnp.abs(l1[0, -1] - l2[0, -1]).max()) < 1e-5


def test_shape_table_is_complete():
    assert set(SHAPES) == {"train_4k", "prefill_32k", "decode_32k", "long_500k"}
    assert SHAPES["long_500k"].seq_len == 524_288
    assert SHAPES["train_4k"].global_batch == 256
