"""Where JAX keeps its persistent compilation cache: one rule for every
entry point (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``).

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets nothing, so the cache lands there and nowhere else.
* unset: the cache goes to ``.jax_cache/`` at the root of the checkout
  (git-ignored). The path is fixed so that a later run of the same
  checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root (this file is ``src/repro/launch/compile_cache.py``)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    DEFAULT_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
