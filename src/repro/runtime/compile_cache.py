"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, the ``benchmarks/`` and ``examples/``
scripts) call ``enable_compile_cache()`` once before their first
compilation; importing this module changes nothing.  The default
directory is fixed: a tmp, pid or time-based path would hold nothing the
next run could find again.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache — this file is <checkout>/src/repro/runtime/...
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache goes to ``.jax_cache`` at the
    root of the checkout, which git ignores."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
