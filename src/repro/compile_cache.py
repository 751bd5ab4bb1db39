"""Persistent XLA compilation cache shared by every entry point.

`enable()` is called by each script before its first compile
(`chip_smoke.py`, `examples/*.py`, `benchmarks/run.py`). It is not run on
import, so `import repro` stays free of jax.
"""
from __future__ import annotations

import os
from pathlib import Path

#: fixed cache directory of this checkout (src/repro/ -> checkout root):
#: the path is part of the cache key, so it never comes from a temp
#: name, a pid or the time
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
    nothing else is set. Otherwise the cache lives at `DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
