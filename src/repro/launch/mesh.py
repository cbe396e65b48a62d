"""Mesh construction.

Functions, not module-level constants: importing this module never
touches jax device state.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _axis_kw(n: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n}


def make_smoke_mesh():
    """(1, n) mesh over the local devices with the serving/training axis
    names ``data`` and ``model``."""
    n = jax.device_count()
    return jax.make_mesh((1, n), ("data", "model"), **_axis_kw(2))


def make_fleet_mesh():
    """All local devices on one ``data`` axis — the search-fleet layout.

    ``repro.core.tensor_search`` shards its candidate population over
    ``data``, so a single fleet worker drives every chip it can see; the
    per-generation elite selection is the only cross-device collective.
    """
    return jax.make_mesh((jax.device_count(),), ("data",), **_axis_kw(1))
