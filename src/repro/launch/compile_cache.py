"""Where JAX's persistent compilation cache lives.

Every entry point (``repro.launch.serve``, ``benchmarks/run.py``,
``chip_smoke.py``) calls ``enable_compile_cache`` before its first compile,
so a later run of the same programs on the same devices reads them back
instead of compiling again.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is used and no
    other is set.  Otherwise the cache lives at ``.jax_cache`` in the
    checkout (git-ignored).  The path is part of what a later run must find
    again, so it is fixed: never a temporary name, a pid or the time.  The
    variable is exported, so child processes share the cache."""
    path = os.environ.setdefault(ENV, str(DEFAULT_DIR))
    if "jax" in sys.modules:  # jax read its environment when it was imported
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
