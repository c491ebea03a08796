"""Where JAX keeps its persistent compilation cache.

The entry points that drive an accelerator (``chip_smoke.py``,
``benchmarks/run.py``) call ``configure_compile_cache`` at start-up; the
library never does, so importing it changes no JAX setting.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: A fixed directory in the checkout (git-ignored).  Cache entries are
#: only found again at the same path, so it is never built from a
#: temporary name, a process id or the time.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a stable directory and
    return it.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads
    it itself and nothing is changed here."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
