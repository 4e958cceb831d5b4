"""The one place a process picks its persistent XLA compilation cache.

Every process that compiles engine programs (``tunnel serve --backend tpu``,
chip_smoke.py's children) calls :func:`enable` before its first compile, so
they all share one cache and a second start of the same configuration loads
programs instead of compiling them.

Placement rule: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and no directory is set in code — the caller (a chip tool, a
deployment) decides where the cache lives and whether it outlives the
process.  Otherwise the cache is ``<checkout>/.jax_cache`` (git-ignored).
Never a temp name, pid or timestamp: a directory that moves never hits.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def cache_dir() -> str:
    """The directory :func:`enable` selects.  Pure ``os`` — callable from a
    parent process that must stay off JAX (chip_smoke.py counts entries)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", cache_dir())
    return cache_dir()
