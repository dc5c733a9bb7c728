"""Where the entry points keep JAX's persistent compilation cache.

Every entry point (``chip_smoke.py``, ``benchmarks/run.py``, the
``repro.launch`` CLIs, ``examples/*``) calls :func:`use_compile_cache`
first thing in ``main``; importing the library sets nothing.  A set
``JAX_COMPILATION_CACHE_DIR`` wins (JAX reads it itself); otherwise the
cache lives in ``.jax_cache/`` at the repository root -- a fixed path,
since the path is part of each entry's key and a moving directory never
hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
