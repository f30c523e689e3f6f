"""Where the persistent compilation cache goes: ``JAX_COMPILATION_CACHE_DIR``
when it is set, else the fixed ``<repo>/.jax_cache``; and that its key
holds the programs' metadata, so scopes are never stale."""

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
print(jax.config.jax_compilation_cache_include_metadata_in_key)
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
    assert _run(where) == [where, where, "True"]
    assert os.listdir(where), "nothing was written to the cache"


def test_default_cache_is_fixed_under_the_repo():
    want = os.path.join(REPO, ".jax_cache")
    assert str(compile_cache.DEFAULT_DIR) == want
    assert _run(None) == [want, want, "True"]


_SCOPED = """
import sys
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
enable_compile_cache()

def f(x):
    with jax.named_scope(sys.argv[1]):
        return jnp.sin(x) * 2

print(jax.jit(f).lower(jnp.ones(8)).compile().as_text())
"""


def test_a_cached_program_keeps_its_own_scopes(tmp_path):
    """Two programs that differ only in a named scope get keys of their
    own: the second does not load the first's executable and its
    metadata."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=os.path.join(REPO, "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    for scope in ("first_scope", "second_scope"):
        proc = subprocess.run([sys.executable, "-c", _SCOPED, scope], env=env,
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert f"/{scope}/" in proc.stdout
    assert len(os.listdir(tmp_path / "cache")) >= 2
