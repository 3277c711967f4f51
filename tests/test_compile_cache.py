"""The persistent compilation cache: the environment's directory when one is
set, else one fixed directory in the checkout that git ignores."""

from __future__ import annotations

import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent

_PROBE = """
import jax, jax.numpy as jnp
from repro import compile_cache
print(compile_cache.enable())
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
"""


def _probe(env_dir):
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"),
               JAX_PLATFORMS="cpu",
               # cache every compile, however small or quick
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_cache_goes_where_the_environment_says(tmp_path):
    enabled, configured = _probe(tmp_path)
    assert enabled == configured == str(tmp_path)
    assert any(tmp_path.iterdir()), "no cache entry was written"


def test_cache_defaults_to_a_fixed_ignored_dir_in_the_checkout():
    from repro import compile_cache

    assert compile_cache.CACHE_DIR == REPO / ".jax_cache"
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
    enabled, configured = _probe(None)
    assert enabled == configured == str(REPO / ".jax_cache")
