"""Logical-axis sharding: the one place mesh layout decisions live.

Model code annotates tensors with *logical* axis names (``'batch'``,
``'heads'``, ``'ffn'``, …).  A :class:`ShardingContext` resolves those to
mesh axes under the active mesh, with a divisibility guard: a logical axis
whose dimension does not divide by its mesh extent falls back to
replication instead of producing uneven shards (e.g. whisper's prime-ish
vocab).  Outside any context every annotation is a no-op, so the same
model code runs single-device tests and multi-chip meshes unchanged.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from dataclasses import dataclass, field

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> preferred mesh axes (in priority order; filtered by mesh)
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),        # data parallel (pod is outer DP)
    "fsdp": ("data",),               # weight/optimizer-state sharding
    "model": ("model",),             # tensor parallel
    "expert": ("data",),             # expert parallelism (MoE dispatch)
    "expert_fsdp": ("data",),        # expert-stack weight sharding
    "cache_seq": ("data",),          # context-parallel long KV caches
}


def axis_extent(name: str) -> int:
    """Mesh extent a logical axis would shard over (1 outside a context)."""
    ctx = current()
    if ctx is None:
        return 1
    extent = 1
    for a in ctx.rules.get(name, ()):
        if a in ctx.mesh.axis_names:
            extent *= ctx.mesh.shape[a]
    return extent


@dataclass(frozen=True)
class ShardingContext:
    mesh: Mesh
    rules: dict[str, tuple[str, ...]] = field(default_factory=lambda: dict(DEFAULT_RULES))

    def resolve(self, shape: tuple[int, ...], axes: tuple[str | None, ...]) -> P:
        """Logical names -> PartitionSpec with divisibility fallback."""
        assert len(shape) == len(axes), (shape, axes)
        parts: list = []
        for dim, name in zip(shape, axes):
            if name is None:
                parts.append(None)
                continue
            mesh_axes = tuple(
                a for a in self.rules.get(name, ()) if a in self.mesh.axis_names
            )
            extent = 1
            for a in mesh_axes:
                extent *= self.mesh.shape[a]
            if not mesh_axes or extent <= 1 or dim % extent != 0:
                parts.append(None)  # replicate rather than shard unevenly
            else:
                parts.append(mesh_axes if len(mesh_axes) > 1 else mesh_axes[0])
        return P(*parts)


_state = threading.local()


def current() -> ShardingContext | None:
    return getattr(_state, "ctx", None)


@contextmanager
def activate(mesh: Mesh, rules: dict[str, tuple[str, ...]] | None = None):
    prev = current()
    _state.ctx = ShardingContext(mesh, {**DEFAULT_RULES, **(rules or {})})
    try:
        yield _state.ctx
    finally:
        _state.ctx = prev


def shard(x: jax.Array, *axes: str | None) -> jax.Array:
    """Annotate ``x`` with logical axes; no-op outside a context."""
    ctx = current()
    if ctx is None:
        return x
    spec = ctx.resolve(x.shape, axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


def spec_for_logical(shape: tuple[int, ...], axes: tuple[str | None, ...]) -> P:
    """Resolve a spec under the active context (replicated if none)."""
    ctx = current()
    if ctx is None:
        return P()
    return ctx.resolve(shape, axes)
