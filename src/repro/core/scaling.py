"""Scaling-vector determination (Alg. 1 step III; paper SIII-B).

Two modes, both for real and complex operands:

* fast  — Cauchy-Schwarz bound on the row/column 2-norms of the block
          embedding (paper eqs. 11-12).  One pass over A and B.
* accu  — auxiliary 7-bit int8 product bounds sum_h |a'||b'| directly
          (paper eqs. 13-14).  Tighter => fewer moduli for target accuracy.

All scale factors are exact powers of two; we carry their integer exponents
(the paper stores them as INT16) and materialize mu = 2^e exactly
(`exp2i`).  Neither `frexp_exponent` nor `exp2i` reinterprets f64 bits:
XLA's TPU x64 rewriter cannot lower an f64 <-> s64 bitcast, which is what
`jnp.frexp` / `jnp.ldexp` emit.

GPU->TPU adaptation: the paper bounds CUDA's __log2f error with
delta = 0.5/(1-4u) in round-down/round-up mode; we use f64 log2 with an
explicit safety factor DELTA = 0.5*(1+2^-40) and floor() — same contract
(the computed bound always over-estimates log2 of the true norm).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .intmul import int8_matmul
from .moduli import CRTContext

DELTA = 0.5 * (1.0 + 2.0**-40)
_F64 = jnp.float64


@jax.jit  # one dispatch for eager callers; inlined under an outer jit
def frexp_exponent(x: jnp.ndarray) -> jnp.ndarray:
    """The int32 exponent of `np.frexp(x)`: x = f * 2^e with |f| in [0.5, 1).

    Zero, inf and nan give 0, as in NumPy.  floor(log2 |x|) can be one off
    next to a power of two; two exact comparisons with `exp2i` powers fix
    it, so every normal f64 (the whole range [2^-1022, 2^1024), far outside
    f32's) matches `np.frexp` bitwise.  f64 subnormals count as zero, as
    XLA's arithmetic flushes them (not always its compares, hence the
    explicit test).
    """
    x = jnp.abs(jnp.asarray(x, _F64))
    normal = jnp.isfinite(x) & (x > 0) & (x >= np.finfo(np.float64).tiny)
    safe = jnp.where(normal, x, 1.0)
    e = jnp.floor(jnp.log2(safe)).astype(jnp.int32)
    e = e - (safe < exp2i(e)).astype(jnp.int32)
    e = e + (safe >= exp2i(e + 1)).astype(jnp.int32)
    return jnp.where(normal, e + 1, 0)


def ilogb(x: jnp.ndarray) -> jnp.ndarray:
    """floor(log2 |x|) for normal x != 0, exact (paper uses ilogb())."""
    return frexp_exponent(x) - 1


@jax.jit
def exp2i(e: jnp.ndarray) -> jnp.ndarray:
    """2^e in f64 for integer e: bitwise `np.ldexp(1.0, e)` wherever that
    is a normal f64 (e in [-1022, 1023]), inf above and 0 below (XLA
    flushes subnormal results).  A product of exact powers of two, one per
    set bit of |e|, so no bits are reinterpreted."""
    e = jnp.asarray(e, jnp.int32)
    mag = jnp.abs(jnp.clip(e, -1100, 1100))
    r = jnp.ones(e.shape, _F64)
    for j in range(10):  # bits 2^0 .. 2^9 cover |e| <= 1023
        k = 1 << j
        step = jnp.where(e < 0, 2.0**-k, 2.0**k)
        r = r * jnp.where((mag & k) != 0, step, 1.0)
    return jnp.where(e > 1023, jnp.inf, jnp.where(e < -1022, 0.0, r))


def _p_fast(ctx: CRTContext) -> float:
    # P'_fast = (log2(P-1) - 1)/2 - 1  (precomputed host-side)
    return (ctx.log2_P - 1.0) / 2.0 - 1.0


def _p_accu(ctx: CRTContext) -> float:
    # P'_accu = log2(P-1)/2 - 0.5
    return ctx.log2_P / 2.0 - 0.5


def _fast_exponent(
    absmax: jnp.ndarray, norm2_scaled: jnp.ndarray, ctx: CRTContext
) -> jnp.ndarray:
    """floor(P'fast - max(1, delta*log2(sum a_hat^2))) - ilogb(max|a|).

    `norm2_scaled` is sum of (a * 2^-ilogb(max))^2 per row/col, in [1, 4k] —
    the explicit normalization that the paper folds into __log2f range
    reduction.  Zero rows get exponent 0 (mu = 1).
    """
    e_max = ilogb(jnp.where(absmax > 0, absmax, 1.0))
    t = jnp.maximum(norm2_scaled, 1.0)
    bound = jnp.maximum(1.0, DELTA * jnp.log2(t))
    e = jnp.floor(_p_fast(ctx) - bound).astype(jnp.int32) - e_max
    return jnp.where(absmax > 0, e, 0).astype(jnp.int32)


def scale_fast_real(a: jnp.ndarray, b: jnp.ndarray, ctx: CRTContext):
    """Returns integer exponents (e_mu[m], e_nu[n]); mu = 2^e_mu etc."""
    a = a.astype(_F64)
    b = b.astype(_F64)
    amax = jnp.max(jnp.abs(a), axis=1)
    bmax = jnp.max(jnp.abs(b), axis=0)
    an = a * exp2i(-ilogb(jnp.where(amax > 0, amax, 1.0)))[:, None]
    bn = b * exp2i(-ilogb(jnp.where(bmax > 0, bmax, 1.0)))[None, :]
    e_mu = _fast_exponent(amax, jnp.sum(an * an, axis=1), ctx)
    e_nu = _fast_exponent(bmax, jnp.sum(bn * bn, axis=0), ctx)
    return e_mu, e_nu


def scale_fast_complex(ar, ai, br, bi, ctx: CRTContext):
    """Complex fast mode: block embedding (eq. 6) makes row i and i+m of
    A-hat share norms, so mu stays an m-vector (paper SIII-B)."""
    ar, ai = ar.astype(_F64), ai.astype(_F64)
    br, bi = br.astype(_F64), bi.astype(_F64)
    amax = jnp.maximum(jnp.max(jnp.abs(ar), axis=1), jnp.max(jnp.abs(ai), axis=1))
    bmax = jnp.maximum(jnp.max(jnp.abs(br), axis=0), jnp.max(jnp.abs(bi), axis=0))
    sa = exp2i(-ilogb(jnp.where(amax > 0, amax, 1.0)))[:, None]
    sb = exp2i(-ilogb(jnp.where(bmax > 0, bmax, 1.0)))[None, :]
    na = jnp.sum((ar * sa) ** 2 + (ai * sa) ** 2, axis=1)
    nb = jnp.sum((br * sb) ** 2 + (bi * sb) ** 2, axis=0)
    e_mu = _fast_exponent(amax, na, ctx)
    e_nu = _fast_exponent(bmax, nb, ctx)
    return e_mu, e_nu


def _bar_int8(x_abs: jnp.ndarray, e_bar: jnp.ndarray, axis: int) -> jnp.ndarray:
    """ceil(|x| * 2^e_bar) as int8 (<= 64; 7-bit upper-bound matrix)."""
    shape = [1] * x_abs.ndim
    shape[axis] = -1
    v = jnp.ceil(x_abs * exp2i(e_bar).reshape(shape))
    return jnp.clip(v, 0, 127).astype(jnp.int8)


def _accu_exponent(cbar_max: jnp.ndarray, e_bar: jnp.ndarray, ctx: CRTContext):
    t = jnp.maximum(cbar_max.astype(_F64), 1.0)
    e = jnp.floor(_p_accu(ctx) - DELTA * jnp.log2(t)).astype(jnp.int32)
    return e + e_bar


def accu_bound_real(x: jnp.ndarray, side: str):
    """One operand's accurate-mode 7-bit bound: (bar, e_bar, nonzero).

    side='left' bounds rows of A, side='right' columns of B.  This is the
    only accurate-mode quantity that depends on one operand alone, which is
    why `PreparedOperand` can store it (the exponents themselves couple both
    operands through `cbar` and must be recomputed per call).
    """
    x = x.astype(_F64)
    xmax = jnp.max(jnp.abs(x), axis=1 if side == "left" else 0)
    # scale so the max-abs integer part fits 6 bits: max*2^e in [32, 64)
    e_bar = 5 - ilogb(jnp.where(xmax > 0, xmax, 1.0))
    bar = _bar_int8(jnp.abs(x), e_bar, 0 if side == "left" else 1)
    return bar, e_bar, xmax > 0


def accu_bound_complex(xr: jnp.ndarray, xi: jnp.ndarray, side: str):
    """Complex twin of `accu_bound_real`: ((bar_r, bar_i), e_bar, nonzero)."""
    xr, xi = xr.astype(_F64), xi.astype(_F64)
    red = 1 if side == "left" else 0
    xmax = jnp.maximum(
        jnp.max(jnp.abs(xr), axis=red), jnp.max(jnp.abs(xi), axis=red)
    )
    e_bar = 5 - ilogb(jnp.where(xmax > 0, xmax, 1.0))
    axis = 0 if side == "left" else 1
    bar_r = _bar_int8(jnp.abs(xr), e_bar, axis)
    bar_i = _bar_int8(jnp.abs(xi), e_bar, axis)
    return (bar_r, bar_i), e_bar, xmax > 0


def accu_cbar_complex(abar, bbar) -> jnp.ndarray:
    """Paper SIII-B accurate mode: Cbar_I = AbarI BbarR + AbarR BbarI,
    Cbar_R = Cbar_I + (AbarR - AbarI)(BbarR - BbarI); returns max(R, I)."""
    abar_r, abar_i = abar
    bbar_r, bbar_i = bbar
    cbar_i = int8_matmul(abar_i, bbar_r) + int8_matmul(abar_r, bbar_i)
    # (AbarR - AbarI) etc. are error-free in int8 (values in [-64, 64])
    cbar_r = cbar_i + int8_matmul(abar_r - abar_i, bbar_r - bbar_i)
    return jnp.maximum(cbar_r, cbar_i)


def accu_exponents(
    cbar, e_abar, e_bbar, a_nz, b_nz, ctx: CRTContext,
    row_combine=None, col_combine=None,
):
    """cbar bound -> (e_mu, e_nu) integer exponents.

    `row_combine` / `col_combine` are optional collectives for sharded
    execution: cbar's row max only covers this shard's output columns (and
    the col max this shard's rows), so a shard combines them (`lax.pmax`,
    exact on int32) across the n- and m-sharded mesh axes before the
    exponent formula.  With both None this is exactly the paper's
    single-device computation.
    """
    rmax = jnp.max(cbar, axis=1)
    cmax = jnp.max(cbar, axis=0)
    if row_combine is not None:
        rmax = row_combine(rmax)
    if col_combine is not None:
        cmax = col_combine(cmax)
    e_mu = _accu_exponent(rmax, e_abar, ctx)
    e_nu = _accu_exponent(cmax, e_bbar, ctx)
    return jnp.where(a_nz, e_mu, 0), jnp.where(b_nz, e_nu, 0)


def scale_accurate_real(
    a: jnp.ndarray, b: jnp.ndarray, ctx: CRTContext,
    row_combine=None, col_combine=None,
):
    abar, e_abar, a_nz = accu_bound_real(a, "left")
    bbar, e_bbar, b_nz = accu_bound_real(b, "right")
    cbar = int8_matmul(abar, bbar)  # exact upper bound of sum mu|a| nu|b|
    return accu_exponents(
        cbar, e_abar, e_bbar, a_nz, b_nz, ctx, row_combine, col_combine
    )


def scale_accurate_complex(
    ar, ai, br, bi, ctx: CRTContext, row_combine=None, col_combine=None
):
    abar, e_abar, a_nz = accu_bound_complex(ar, ai, "left")
    bbar, e_bbar, b_nz = accu_bound_complex(br, bi, "right")
    cmax = accu_cbar_complex(abar, bbar)
    return accu_exponents(
        cmax, e_abar, e_bbar, a_nz, b_nz, ctx, row_combine, col_combine
    )
