"""The entry points' persistent compilation cache: JAX's own directory
when ``JAX_COMPILATION_CACHE_DIR`` is set, else a fixed one in the
checkout. Each case compiles in a fresh interpreter, since JAX reads
the variable once, at import."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]

_COMPILE = """
import sys
import jax, jax.numpy as jnp
from repro.launch import compile_cache
if len(sys.argv) > 1:
    compile_cache.DEFAULT_DIR = sys.argv[1]
print(compile_cache.enable_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)).block_until_ready()
"""


def test_default_dir_is_fixed_in_checkout():
    assert compile_cache.DEFAULT_DIR == ROOT / ".jax_cache"


@pytest.mark.parametrize("env_set", [True, False])
def test_entries_land_in_the_chosen_dir(tmp_path, env_set):
    env_dir, default_dir = tmp_path / "env", tmp_path / "default"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", _COMPILE, str(default_dir)], env=env,
        capture_output=True, text=True, timeout=120, check=True)
    want, other = (env_dir, default_dir) if env_set else (default_dir,
                                                          env_dir)
    assert out.stdout.splitlines()[-1] == str(want)
    assert any(want.iterdir())
    assert not other.exists()
