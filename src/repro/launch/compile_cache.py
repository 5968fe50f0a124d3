"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (``chip_smoke.py``, :func:`repro.launch.serve.main`, the
benchmark mains) call :func:`enable_compile_cache` before their first
compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read
it and the helper sets nothing.  Otherwise the cache lives in
``<checkout>/.jax_cache``: a fixed path, since the path is part of what a
later process must find again.  The persistent cache covers ``jax.jit``
calls and the ahead-of-time ``.lower(...).compile()`` calls of
:class:`repro.core.jdob.ExecutableCache` alike; JAX persists only
programs whose compile took at least
``jax_persistent_cache_min_compile_time_secs`` (1 s by default).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root: src/repro/launch/ -> three levels up
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
