"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` before their first
compile. ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it
itself and nothing is changed here. Otherwise the cache lives at
``<repo>/.jax_cache``, a fixed path, because the directory is part of
what makes a later run find the entry again.

The key includes each program's metadata. The program's device work is
named by ``jax.named_scope`` (``repro.runtime.tracing``), and only the
metadata differs where a scope moves; a key without it would load an
executable compiled from older code, whose profile names its ops as
that code did.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
