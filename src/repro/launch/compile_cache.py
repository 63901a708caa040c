"""Where the entry points keep JAX's persistent compilation cache.

Compiling the emulated GEMM programs and a model's train step for the TPU
takes minutes; the persistent cache makes every later run of the same
program skip it.  Entry points (the launchers, the benchmarks, the chip
smoke test) call `enable_compile_cache` once at start; importing `repro`
sets no cache.

* With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable itself and
  nothing is set here.
* Otherwise the cache goes to ``<checkout>/.jax_cache`` (git-ignored).  The
  path is fixed, since a cache directory that moves between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the in-checkout cache directory used when the environment names none
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
