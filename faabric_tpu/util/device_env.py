"""Where compiled device programs are kept between processes.

Every entry point that initialises a JAX backend calls
:func:`configure_compile_cache` first, so a worker restart, a second
bench child or a repeated smoke run finds the programs the last process
compiled instead of compiling them again.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads it itself, so nothing
    is set in code. Unset, the cache lives at ``<checkout>/.jax_cache``
    — a fixed path: a directory that moves (temp name, pid, time) is
    never found again by the next process.
    """
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    import jax

    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
