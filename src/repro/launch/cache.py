"""Where JAX keeps its persistent compilation cache."""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", ".."))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    `JAX_COMPILATION_CACHE_DIR`, when set, is JAX's own setting and is left
    alone.  Otherwise the cache lives at the fixed `.jax_cache/` of this
    checkout: the path is part of what the cache matches on, so it must not
    move between runs (no temp names, pids or times).  Tests never call
    this; they run without the cache."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
