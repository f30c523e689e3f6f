"""Where the persistent compilation cache goes: ``JAX_COMPILATION_CACHE_DIR``
when it is set, else the fixed ``<repo>/.jax_cache``."""

from __future__ import annotations

import os
import subprocess
import sys

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SNIPPET = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
"""


def _run(env_dir: str | None) -> list[str]:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run([sys.executable, "-c", _SNIPPET], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.split()


def test_cache_goes_where_the_environment_says(tmp_path):
    where = str(tmp_path / "cache")
    assert _run(where) == [where, where]
    assert os.listdir(where), "nothing was written to the cache"


def test_default_cache_is_fixed_under_the_repo():
    want = os.path.join(REPO, ".jax_cache")
    assert str(compile_cache.DEFAULT_DIR) == want
    assert _run(None) == [want, want]
