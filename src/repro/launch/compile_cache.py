"""Where JAX keeps its persistent compilation cache.

Entry points (``python -m repro.launch.serve``, ``python -m repro.fleet``,
``chip_smoke.py``) call :func:`enable_compile_cache` before their first
compile.  ``JAX_COMPILATION_CACHE_DIR``, when set, names the cache and no
other directory is used.  Otherwise the cache lives at a fixed path inside
the checkout, ``<repo>/.jax_cache`` (ignored by git): a fixed path lets a
run find the entries an earlier run wrote, where a path made from a temp
name, a pid or the time would start every run with an empty cache.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]


def compile_cache_dir(environ=os.environ) -> Path:
    """The cache directory the environment ``environ`` calls for."""
    return Path(environ.get(ENV_VAR) or REPO_ROOT / ".jax_cache")


def enable_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return that directory."""
    import jax

    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path
