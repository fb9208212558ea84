"""JAX's persistent compilation cache for the entry points that compile for
the chip (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.serve_emulation``).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set here.  Otherwise the cache lives in ``.jax_cache/`` at the root of
the checkout (git-ignored).  The path is part of what the cache is keyed
on, so it is fixed: never a temporary name, a pid or a timestamp.
"""

from __future__ import annotations

import os
import pathlib

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Switch the persistent compilation cache on; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    CHECKOUT_CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
