"""JAX's persistent compilation cache, at one fixed place.

Entry points (``chip_smoke.py``, ``repro.launch.trainer.main``,
``repro.launch.serve.main``) call :func:`enable`; importing a library
module never does.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and nothing here overrides it.  Otherwise the cache lives in
``.jax_cache`` at the root of the checkout — a fixed path, because the
path is part of what a later process must find again (a temporary,
pid-named or time-named directory never hits).
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
