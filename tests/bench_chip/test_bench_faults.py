"""Whole runs on the CPU at small size with the timed path broken under
the harness: each fault a cell can have must turn ``correct`` false,
while the unbroken run stays correct."""

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import serve

import tiny


def break_engine(monkeypatch, fault):
    orig = serve.build_engine

    def build(*a, **k):
        engine, tel = orig(*a, **k)
        step = engine._jit_step
        engine._jit_step = lambda *args: fault(step, args)
        return engine, tel

    monkeypatch.setattr(serve, "build_engine", build)


def altered_token(step, args):
    """A token altered where it is produced: token 7 always wins."""
    logits, caches = step(*args)
    return logits.at[:, 7].add(1e4), caches


def half_batch(step, args):
    """Half of the batch left out: its slots' logits never computed."""
    logits, caches = step(*args)
    return logits.at[logits.shape[0] // 2:].set(0.0), caches


def stale_state(step, args):
    """A step that returns its state unchanged: the KV pages it was
    handed (the step donates them, so a copy is kept)."""
    kept = jax.tree.map(jnp.copy, args[1])
    logits, _ = step(*args)
    return logits, kept


SERVE_FAULTS = {f.__name__: f for f in (altered_token, half_batch,
                                        stale_state)}


@pytest.mark.parametrize("fault", sorted(SERVE_FAULTS))
def test_a_broken_serving_step_is_not_correct(fault, tmp_path, monkeypatch):
    cell = tiny.cell(tmp_path, "qwen3-tiny-w4", "tiny-batch")
    break_engine(monkeypatch, SERVE_FAULTS[fault])
    res = tiny.run(cell, monkeypatch, tmp_path)
    assert not res["correct"]
    gap = res["check"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]
