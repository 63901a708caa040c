"""Paper Fig. 1: the four INT8 complex-multiplication strategies.

  block_a    — one (2h, 2h) x (2h, h) real GEMM per modulus (eq. 7)
  block_b    — one (h, 2h) x (2h, 2h) real GEMM per modulus (eq. 8)
  karatsuba  — three (h, h, h) GEMMs per modulus (eq. 10)
  karatsuba8k— same with n-blocking (paper: blocks of 8192; scaled here)

We measure wall time on this host (CPU) and report the derived effective
INT8 ops/s plus the algorithmic op counts (which is what Fig. 1's ranking
follows on a saturated matrix engine: Karatsuba does 3h^3 multiplies vs
4h^3 for the block embeddings).
"""
from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

from repro.core.cgemm import ozaki2_cgemm
from repro.core.perfmodel import TPU_V5E, select_formulation

from .common import emit, phi_matrix, time_fn


def run(h: int = 512, n_moduli: int = 4):
    rng = np.random.default_rng(0)
    a = jnp.asarray(phi_matrix(rng, (h, h), 0.5, np.complex64))
    b = jnp.asarray(phi_matrix(rng, (h, h), 0.5, np.complex64))
    picked = select_formulation(h, h, h, n_moduli, hw=TPU_V5E, prec="c")
    emit(f"fig1/auto_pick/h{h}", 0.0, f"perfmodel_choice={picked}")
    results = {}
    for name, kwargs in [
        ("block_a", dict(formulation="block_a")),
        ("block_b", dict(formulation="block_b")),
        ("karatsuba", dict(formulation="karatsuba")),
        ("karatsuba_blocked", dict(formulation="karatsuba", n_block=max(128, h // 4))),
    ]:
        fn = functools.partial(
            ozaki2_cgemm, n_moduli=n_moduli, mode="fast", **kwargs
        )
        us = time_fn(fn, a, b)
        int8_muls = (4 if name.startswith("block") else 3) * n_moduli * h**3
        results[name] = us
        emit(
            f"fig1/{name}/h{h}",
            us,
            f"int8_mul_ops={int8_muls:.3e};eff_ops_per_s={int8_muls/(us*1e-6):.3e}",
        )
    return results


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
