"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, the examples) call
``use_persistent_compile_cache()`` once at start-up; importing a library
module never does.  The rule:

  * ``JAX_COMPILATION_CACHE_DIR`` set in the environment: JAX reads it
    itself, and nothing is set here;
  * otherwise: the fixed directory ``.jax_cache/`` at the root of the
    checkout (listed in ``.gitignore``).  A later run finds the entries
    only where the earlier one left them, so the path never moves: never
    a temporary, per-process or timestamped directory.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_persistent_compile_cache() -> str:
    """Apply the rule above; returns the directory in use."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
