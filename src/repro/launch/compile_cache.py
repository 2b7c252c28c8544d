"""Where JAX keeps its persistent compilation cache for this checkout."""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as JAX reads it and
    nothing is set here.  Otherwise the cache goes to ``.jax_cache/`` in the
    checkout: a fixed path, because a cache directory that moves between
    runs never hits.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      str(CHECKOUT / ".jax_cache"))
