"""JAX's persistent compilation cache at a fixed path.

The cache key includes the directory, so a path that changes between
runs (a temporary name, a pid, a time stamp) never hits. Entry points
that compile for the chip call :func:`enable_compile_cache` before their
first compile.

The key also covers the program's debug metadata: each op's ``op_name``
(its ``jax.named_scope`` path) and source location. JAX leaves it out of
the key by default, so two versions of a program that differ only in
their scopes would share an entry, and whichever compiled second would
run under the other's op names: a profiler trace of it would name its
device ops by scopes its code no longer has.
"""
from __future__ import annotations

import os

import jax

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DEFAULT_CACHE_DIR = os.path.join(REPO, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    A set ``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads it itself, and no
    other directory is configured here. Otherwise the cache lives in
    ``<repo>/.jax_cache``.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
