"""Where the persistent XLA compile cache lives.

Every entry point (chip_smoke.py, bench.py, run_model, tools/train,
tools/serve, tools/retrieve) calls `configure_compile_cache()` before its
first jit. The directory is part of the cache key, so it must be the same
on every run: either the one the operator names through
`JAX_COMPILATION_CACHE_DIR` (JAX reads that itself — nothing is set in
code then), or a fixed path inside the checkout.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def configure_compile_cache() -> str:
    """Returns the cache directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
