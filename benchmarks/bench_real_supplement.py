"""Paper SIV-C: real-valued DGEMM emulation supplement.

  * measured: Ozaki-II real f64 emulation fast/accu, with and without
    n-blocking, on this host at small sizes (correctness-bearing timing);
  * model: blocked-vs-unblocked and Ozaki-I slice comparison at 16384^3
    on GH200 constants (paper: blocked fast-N 72-93 TFLOPS vs Ozaki-I
    20-39 TFLOPS vs native DGEMM 61 TFLOPS).
"""
from __future__ import annotations

import functools

import numpy as np
import jax.numpy as jnp

from repro.core import ozaki2_gemm
from repro.core.perfmodel import GH200, real_tflops

from .common import emit, phi_matrix, time_fn


def run(s: int = 384):
    rng = np.random.default_rng(2)
    a = jnp.asarray(phi_matrix(rng, (s, s), 1.0, np.float64))
    b = jnp.asarray(phi_matrix(rng, (s, s), 1.0, np.float64))
    ref = np.asarray(a, np.float64).astype(np.longdouble) @ np.asarray(
        b, np.float64
    ).astype(np.longdouble)
    for mode in ("fast", "accu"):
        for nb in (None, 128):
            nm = 16 if mode == "fast" else 15
            fn = functools.partial(ozaki2_gemm, n_moduli=nm, mode=mode, n_block=nb)
            us = time_fn(fn, a, b)
            c = np.asarray(fn(a, b))
            err = float(np.max(np.abs(c - ref) / np.maximum(np.abs(ref), 1e-300)))
            emit(
                f"sIVC/measured/dgemm/{mode}-{nm}/block{nb or 0}",
                us,
                f"maxrel={err:.2e};tflops={2 * s**3 / (us * 1e-6) * 1e-12:.4f}",
            )
    for nm in (14, 16, 18):
        tf = real_tflops(16384, 16384, 16384, nm, GH200, "fast")
        emit(f"sIVC/model/gh200/fast-{nm}", 0.0,
             f"tflops={tf:.0f};paper_range=63-93;native_dgemm=61")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
