"""Where XLA's persistent compilation cache lives.

Every server start compiles the route step once per pow2 ingest bucket
and per program variant; without a persistent cache each start pays all
of it again. One rule, applied by every process that owns a device
(`python -m emqx_tpu`): if the operator placed the
cache with ``JAX_COMPILATION_CACHE_DIR``, jax reads that variable itself
and nothing is set in code; otherwise the cache sits at ONE fixed path
inside the checkout. The directory is part of the cache key, so a path
that moves (tempfile, pid, time) would never hit.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache",
)


def cache_dir() -> str:
    """The directory in effect (no jax import: drivers may ask too)."""
    return os.environ.get(ENV_VAR) or DEFAULT_DIR


def place_compile_cache() -> str:
    """Call before the first jit. Returns the directory in effect."""
    if not os.environ.get(ENV_VAR):
        import jax

        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return cache_dir()
