"""Where JAX's persistent compilation cache lives.

A compiled program is keyed, among other things, by the cache path, so
the path is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when the environment
sets it (JAX reads that variable itself, and nothing here overrides it),
otherwise ``<checkout>/.jax_cache/``.  Entry points call
:func:`enable_compile_cache` from ``main()``; importing this module
changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def cache_dir(env: Mapping[str, str] = os.environ) -> str:
    """The cache directory this process uses under ``env``."""
    return env.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on at :func:`cache_dir` and
    return that directory."""
    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        DEFAULT_DIR.mkdir(exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
