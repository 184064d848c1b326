"""Where JAX's persistent compilation cache lives — one rule for every
entry point that runs on the chip (``serve.main``, ``chip_smoke.py``).

``$JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
nothing else is configured here. Otherwise the cache goes to one fixed
directory inside the checkout (``.jax_cache/``, listed in ``.gitignore``).
The path is part of the cache key, so it is never made from a temporary
name, a pid or the time: a directory that moves never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``).
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Call before the first compilation of the process. Without
    ``$JAX_COMPILATION_CACHE_DIR`` the package must run from a source
    checkout (``pyproject.toml`` beside ``.jax_cache/``): an installed
    copy has no checkout to keep its cache in, and raises."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    if not (CHECKOUT_CACHE_DIR.parent / "pyproject.toml").is_file():
        raise RuntimeError(
            f"{CHECKOUT_CACHE_DIR.parent} is not a source checkout (no "
            "pyproject.toml): set JAX_COMPILATION_CACHE_DIR to choose where "
            "compiled programs are kept"
        )
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
