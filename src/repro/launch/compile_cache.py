"""Where JAX's persistent compilation cache lives.

One rule for every entry point (``chip_smoke.py``, ``benchmarks/run.py``,
``launch/serve.py``, ``launch/train.py``), applied from their ``main()``
and never at import: ``$JAX_COMPILATION_CACHE_DIR`` when it is set,
otherwise the fixed ``<checkout>/.jax_cache`` (git-ignored).  The path is
part of the cache key, so it is never built from a temp name, a pid or
the time.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one location and
    return that path."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
