"""Where the persistent compilation cache goes: the environment's
directory when it names one, else one fixed path inside the checkout."""

import os
import subprocess
import sys

import pytest

from repro.launch.compile_cache import (ENV_VAR, REPO_ROOT,
                                        compile_cache_dir)


def test_cache_dir_honours_the_environment(tmp_path):
    assert compile_cache_dir({ENV_VAR: str(tmp_path)}) == tmp_path


def test_cache_dir_defaults_to_a_fixed_ignored_path_in_the_checkout():
    path = compile_cache_dir({})
    assert path == REPO_ROOT / ".jax_cache"
    assert (REPO_ROOT / "pyproject.toml").is_file()
    assert compile_cache_dir({ENV_VAR: ""}) == path
    ignored = (REPO_ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "default"])
def test_enable_compile_cache_points_jax_there(tmp_path, from_env):
    """In a child process, so this suite's own JAX keeps its settings."""
    env = {k: v for k, v in os.environ.items() if k != ENV_VAR}
    env["PYTHONPATH"] = str(REPO_ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    if from_env:
        env[ENV_VAR] = str(tmp_path)
    code = ("import jax; from repro.launch.compile_cache import "
            "enable_compile_cache as e; p = e(); "
            "print(p); print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.split()
    want = str(tmp_path if from_env else REPO_ROOT / ".jax_cache")
    assert out == [want, want]
