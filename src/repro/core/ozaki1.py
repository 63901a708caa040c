"""Ozaki-I scheme (the paper's comparison baseline, SIV: 'OS I-S').

Error-free slicing emulation on int8 engines (Ootomo-Ozaki-Yokota [27] /
cuBLAS 'Fixed Mantissa Control' family): row/col-normalize to [0.5, 1),
peel S signed 7-bit mantissa slices per operand, and accumulate the
S(S+1)/2 cross products with |i+j| < S on the int8 engine:

    C ~= sum_{i+j < S} 2^{-7(i+j+2)} A_i B_j .

Versus Ozaki-II with N moduli (N int8 GEMMs), Ozaki-I needs S(S+1)/2 —
the quadratic-vs-linear gap behind the paper's SIV-B throughput results.
Complex variant uses the same Karatsuba trick (3 real emulations).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .intmul import int8_matmul
from .scaling import exp2i, ilogb

SLICE_BITS = 7
_F64 = jnp.float64


def _slices(x: jnp.ndarray, n_slices: int) -> jnp.ndarray:
    """Peel signed 7-bit slices of |x| < 1: x ~= sum_t q_t 2^{-7(t+1)}."""
    out = []
    r = x
    for t in range(n_slices):
        scale = 2.0 ** (SLICE_BITS * (t + 1))
        q = jnp.trunc(r * scale)  # |q| <= 127 by normalization
        r = r - q / scale
        out.append(q.astype(jnp.int8))
    return jnp.stack(out, axis=0)


@functools.partial(jnp.vectorize, excluded=(2, 3), signature="(m,k),(k,n)->(m,n)")
def _gemm_2d(a, b, n_slices, out_dtype):
    a64 = a.astype(_F64)
    b64 = b.astype(_F64)
    amax = jnp.max(jnp.abs(a64), axis=1)
    bmax = jnp.max(jnp.abs(b64), axis=0)
    e_mu = -(ilogb(jnp.where(amax > 0, amax, 1.0)) + 1)
    e_nu = -(ilogb(jnp.where(bmax > 0, bmax, 1.0)) + 1)
    an = a64 * exp2i(e_mu)[:, None]   # rows in [0.5, 1)
    bn = b64 * exp2i(e_nu)[None, :]
    asl = _slices(an, n_slices)
    bsl = _slices(bn, n_slices)
    acc = jnp.zeros(a.shape[:-1] + (b.shape[-1],), _F64)
    # low-order first so the final additions are the significant ones
    for s in range(n_slices - 1, -1, -1):  # s = i + j
        part = jnp.zeros_like(acc)
        for i in range(s + 1):
            j = s - i
            part = part + int8_matmul(asl[i], bsl[j]).astype(_F64)
        acc = acc + part * 2.0 ** (-SLICE_BITS * (s + 2))
    inv = exp2i(-e_mu)[:, None] * exp2i(-e_nu)[None, :]
    return (acc * inv).astype(out_dtype)


def ozaki1_gemm(
    a: jnp.ndarray, b: jnp.ndarray, n_slices: int = 8, out_dtype=None
) -> jnp.ndarray:
    """Emulated real GEMM, Ozaki-I with S slices: S(S+1)/2 int8 GEMMs."""
    out_dtype = jnp.dtype(out_dtype or a.dtype)
    return _gemm_2d(a, b, int(n_slices), out_dtype)


def ozaki1_cgemm(
    a: jnp.ndarray, b: jnp.ndarray, n_slices: int = 8, out_dtype=None
) -> jnp.ndarray:
    """Complex Ozaki-I via Karatsuba: 3 real emulations (paper SIV-B)."""
    out_dtype = jnp.dtype(out_dtype or a.dtype)
    real_dtype = {"complex64": jnp.float32, "complex128": jnp.float64}[
        jnp.dtype(out_dtype).name
    ]
    ar, ai = jnp.real(a).astype(_F64), jnp.imag(a).astype(_F64)
    br, bi = jnp.real(b).astype(_F64), jnp.imag(b).astype(_F64)
    d = ozaki1_gemm(ar, br, n_slices, _F64)
    e = ozaki1_gemm(ai, bi, n_slices, _F64)
    f = ozaki1_gemm(ar + ai, br + bi, n_slices, _F64)
    cr = (d - e).astype(real_dtype)
    ci = (f - d - e).astype(real_dtype)
    return jax.lax.complex(cr, ci)


def int8_gemm_count(n_slices: int) -> int:
    return n_slices * (n_slices + 1) // 2
