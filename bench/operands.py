"""Seeded operands: the paper's phi-generator (arXiv:2512.08321 SIV-A).

Each entry is (u - 1/2) exp(phi g), u uniform on [0, 1), g standard normal;
complex entries draw the real and imaginary parts independently.  phi sets
the spread of magnitudes, which sets the scaling exponents the emulation
has to cover.  The same seed gives the same operands, in any process.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: row chunks, each from its own child stream, filled in parallel (NumPy's
#: generators release the GIL); fixed, so a seed gives the same operands
#: on any host
_THREADS = 8


def phi_matrix(rng, shape, phi, dtype):
    """One phi-matrix of `dtype`, drawn from `rng` (a NumPy Generator)."""
    u = rng.random(shape)
    g = rng.standard_normal(shape)
    m = (u - 0.5) * np.exp(g * phi)
    if np.issubdtype(np.dtype(dtype), np.complexfloating):
        u2 = rng.random(shape)
        g2 = rng.standard_normal(shape)
        m = m + 1j * (u2 - 0.5) * np.exp(g2 * phi)
    return m.astype(dtype)


def _filled(seed: int, which: int, shape, phi, dtype) -> np.ndarray:
    """Operand `which` of `seed`, its row chunks drawn from child streams."""
    out = np.empty(shape, dtype)
    bounds = np.linspace(0, shape[0], _THREADS + 1).astype(int)
    streams = np.random.SeedSequence([seed, which]).spawn(_THREADS)

    def fill(i):
        lo, hi = bounds[i], bounds[i + 1]
        rng = np.random.default_rng(streams[i])
        out[lo:hi] = phi_matrix(rng, (hi - lo,) + tuple(shape[1:]), phi, dtype)

    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(fill, range(_THREADS)))
    return out


def gemm_operands(seed: int, m: int, n: int, k: int, phi: float, dtype):
    """(A, B) of shapes (m, k) and (k, n) for `seed`, a non-negative int."""
    if seed < 0:
        raise ValueError(f"--seed must be a non-negative integer, got {seed}")
    return (_filled(seed, 0, (m, k), phi, dtype),
            _filled(seed, 1, (k, n), phi, dtype))
