"""JAX's persistent compilation cache, placed once per entry point.

A cold process on a TPU pays seconds to minutes of XLA and Mosaic
compiles before its first dispatch.  The persistent cache keeps them on
disk, keyed by (among other things) the directory path, so a directory
that moves between runs never hits.  Every entry point (``chip_smoke.py``,
the ``repro.launch`` CLIs, the ``benchmarks`` mains) calls
:func:`enable_compile_cache` before its first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set -> JAX already uses that directory
  and this module sets no other;
* otherwise -> the fixed in-checkout directory :data:`DEFAULT_DIR`
  (``<repo>/.jax_cache``, gitignored) — never a temp name, pid or time.

Either way the minimum compile time for an entry drops to zero, so the
small Pallas kernels (well under JAX's one-second default) are cached
too.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
