"""Where JAX's persistent compilation cache lives.

Compiling the serving programs at published widths takes minutes, so the
launchers keep compiled programs across processes.  JAX reads
``JAX_COMPILATION_CACHE_DIR`` itself; when it is not set the cache goes
to ``.jax_cache`` in the checkout — one fixed path, because the
directory is part of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on for this process (before its first
    compile) and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
