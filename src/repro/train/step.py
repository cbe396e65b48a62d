"""Jit-compiled train / prefill / decode step builders.

``make_train_step`` is what the launcher jits:
value_and_grad over the family loss, optional microbatch gradient
accumulation (a ``lax.scan`` over microbatches — decouples global batch
from per-device memory), then the AdamW update.  All functions are pure;
sharding comes from in/out shardings at jit time plus the logical
constraints inside the model code.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from ..models import decode_fn, loss_fn
from ..models.config import ModelConfig
from .optim import OptimizerConfig, apply_updates


def make_train_step(
    cfg: ModelConfig,
    opt_cfg: OptimizerConfig,
    *,
    microbatches: int = 1,
    remat: str = "full",
    backend: str = "auto",
    scan_unroll: bool = False,
):
    loss = loss_fn(cfg)

    def compute_grads(params, batch):
        if microbatches == 1:
            return jax.value_and_grad(
                lambda p: loss(cfg, p, batch, backend=backend, remat=remat,
                               scan_unroll=scan_unroll)
            )(params)

        def micro(carry, mb):
            acc_loss, acc_grads = carry
            l, g = jax.value_and_grad(
                lambda p: loss(cfg, p, mb, backend=backend, remat=remat,
                               scan_unroll=scan_unroll)
            )(params)
            return (acc_loss + l, jax.tree.map(jnp.add, acc_grads, g)), None

        split = jax.tree.map(
            lambda x: x.reshape(microbatches, x.shape[0] // microbatches, *x.shape[1:]),
            batch,
        )
        zero_grads = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        # scan_unroll: every microbatch's ops visible to XLA cost_analysis
        # (which counts a rolled scan body once)
        (total_loss, total_grads), _ = jax.lax.scan(
            micro, (jnp.float32(0.0), zero_grads), split,
            unroll=True if scan_unroll else 1,
        )
        inv = 1.0 / microbatches
        return total_loss * inv, jax.tree.map(lambda g: g * inv, total_grads)

    def train_step(params, opt_state, batch):
        l, grads = compute_grads(params, batch)
        params, opt_state, metrics = apply_updates(opt_cfg, params, grads, opt_state)
        metrics["loss"] = l
        return params, opt_state, metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, *, backend: str = "auto",
                      scan_unroll: bool = False):
    """Forward-only full-sequence step (inference prefill)."""
    from ..models import forward_fn

    fwd = forward_fn(cfg)

    def prefill(params, batch):
        logits, _ = fwd(cfg, params, batch, backend=backend, remat="none",
                        scan_unroll=scan_unroll)
        return logits[:, -1]  # next-token logits

    return prefill


def make_decode_step(cfg: ModelConfig):
    step = decode_fn(cfg)

    def serve_step(params, caches, tokens, pos):
        return step(cfg, params, caches, tokens, pos)

    return serve_step
