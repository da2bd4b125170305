"""Where JAX's persistent compilation cache lives.

The serve path compiles one executable per (chunk bucket, flash mode, slot
bucket); a cold start is minutes of XLA. The cache directory is part of
the cache key, so it must not move between processes or runs: either the
operator places it (`JAX_COMPILATION_CACHE_DIR`, which JAX reads itself —
nothing is set in code then) or it is one fixed path inside the checkout.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """<checkout>/.jax_cache, derived from this package's location."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, ".jax_cache")


def enable_compile_cache() -> str:
    """Call once per process before the first compile. Returns the
    directory in effect. The process's watch of its own program builds
    (obs.PROCESS) is installed here, so it sees that first compile too.

    The key covers the ops' metadata too: the programs' named scopes
    (obs.spans.SCOPE_CATALOG) live there, and JAX's default key ignores
    it, so a cache written by a build with other scopes would hand back
    executables whose device trace names the wrong parts, or none. The
    price is a recompile after an edit that moves a traced line."""
    import jax

    from ..obs import PROCESS
    PROCESS.install()
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get(ENV)
    if placed:
        return placed
    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
