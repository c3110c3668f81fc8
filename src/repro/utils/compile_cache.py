"""JAX's persistent compilation cache for the command-line entry points.

The cache is keyed by its directory too, so it lives at one fixed place:
where `JAX_COMPILATION_CACHE_DIR` is set JAX reads it itself and nothing
is set here; otherwise it is `<checkout>/.jax_cache` (git-ignored).
Library code and tests never call this.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
