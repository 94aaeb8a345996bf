"""Persistent compilation cache placement for the entry points.

A cold serving process spends most of its start-up compiling; JAX's
persistent cache lets the next process on the same machine skip that.
`use_compile_cache()` is called by the entry points (`launch/serve.py`,
`chip_smoke.py`, the benchmarks' `main`s) and never at import:

  * `JAX_COMPILATION_CACHE_DIR` set — JAX already reads it; the helper
    uses that directory as it is and sets nothing else;
  * unset — the cache goes to `.jax_cache/` at the root of the checkout,
    a fixed path (the path is part of what makes entries hit) that
    `.gitignore` lists, and every compiled program is written there.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory (see the module docstring)."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)
