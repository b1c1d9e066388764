"""Where JAX keeps its persistent compilation cache.

One rule, applied by the entry points (``chip_smoke.py`` and the
``repro.launch`` CLIs), never at library import:

  * ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself, and this
    module sets no other directory;
  * otherwise: ``<checkout>/.jax_cache``, a fixed path (the path is part
    of the cache key, so a directory that moves never hits).  It is
    listed in ``.gitignore``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
