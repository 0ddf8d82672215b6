"""Where JAX keeps its persistent compilation cache for this repo's entry
points (``chip_smoke.py``, ``benchmarks/run.py``, the examples).

Called explicitly by those scripts, never on import of ``repro``: a library
import must not change process-wide JAX configuration.
"""
from __future__ import annotations

import os
from pathlib import Path

#: fixed in-checkout cache directory (listed in .gitignore), so that a
#: later run in the same checkout finds what an earlier one compiled.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to :data:`REPO_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
