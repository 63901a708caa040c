"""Pallas kernel: Garner mixed-radix CRT reconstruction + inverse scaling.

TPU-native replacement for the paper's fp64 eq.(5) reconstruction (DESIGN.md
S2): the digit recursion is exact small-integer arithmetic (done in f32 where
every value is < 2^17, hence error-free), and the digit->value conversion
accumulates in a double-single (two-f32, ~48-bit) pair against prescaled
mixed-radix weights W_t * 2^-S, followed by the exact power-of-two inverse
scaling  C = C' / (mu_i nu_j).

Output: 'f32' (CGEMM/SGEMM-grade) or a (2, m, n) double-single pair
('dd', ZGEMM-grade on TPU; ~2^-48 relative — see DESIGN.md S6).

Grid: (S, m/bm, n/bn) with S an optional leading *stack* dimension: a
(S, N, m, n) residue stack reconstructs S outputs sharing the same scale
exponents in one launch — the complex pipeline stacks the CR/CI residue
planes so reconstruction costs one `pallas_call` for the whole complex
output.  (N, m, n) inputs are treated as S=1 and squeezed on return.  The
full N-deep residue stack for a tile sits in VMEM (N * bm * bn int8;
13 * 256 * 256 = 0.8 MiB).  Non-block-divisible m/n are zero-padded to the
block grid and sliced back (zero residues reconstruct to zero).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..core.moduli import CRTContext
from .common import (
    I0,
    block_and_padded,
    interpret_default,
    pad_dims,
    split_scale_exponent,
    sym_mod_f32,
)
from ..core import expansion as ex


def _prescale(ctx: CRTContext) -> int:
    """Weight prescale S keeping W_t * 2^-S * 127 within f32 range."""
    return max(0, math.ceil(ctx.log2_P) - 100)


def _weight_table(ctx: CRTContext) -> np.ndarray:
    """(N, 2) f32 double-single of W_t * 2^-S (exact power-of-two scaling)."""
    s = _prescale(ctx)
    tab = np.zeros((ctx.n, 2), dtype=np.float32)
    W = 1
    for t in range(ctx.n):
        hi = np.float32(np.ldexp(float(W), -s))
        lo = np.float32(np.ldexp(W - int(math.ldexp(float(np.float64(hi)), s)), -s))
        tab[t, 0], tab[t, 1] = hi, lo
        W *= ctx.moduli[t]
    return tab


def garner_tile(planes, rr, cc, *, ctx, out_dd):
    """Garner digits -> double-single value -> inverse scaling, one tile.

    The single implementation of the reconstruction math shared by the
    standalone Garner kernel and the fused megakernel epilogues: both run
    literally these ops, so their outputs are bitwise identical.

    `planes` is a list of N (bm, bn) f32 canonical residue tiles of C';
    `rr`/`cc` the broadcast-ready inverse-scale factor products (already
    shaped (bm, 1) / (1, bn)).  Returns the (bm, bn) f32 tile, or the
    (hi, lo) double-single pair when `out_dd`.
    """
    moduli = ctx.moduli
    n = ctx.n
    # --- Garner digits (exact f32 integer arithmetic, all values < 2^17) ---
    digits = []
    for t in range(n):
        pf, half = float(moduli[t]), float((moduli[t] - 1) // 2)
        r = planes[t]
        for s in range(t):
            r = sym_mod_f32((r - digits[s]) * float(ctx.garner_inv[s, t]), pf, half)
        digits.append(r)
    # --- digits -> value, double-single accumulation, MS digit first ---
    wt = _weight_table(ctx)
    hi = jnp.zeros_like(digits[0])
    lo = jnp.zeros_like(digits[0])
    for t in range(n - 1, -1, -1):
        ph, pe = ex.two_prod(jnp.float32(wt[t, 0]), digits[t])
        pe = pe + jnp.float32(wt[t, 1]) * digits[t]
        hi, lo = ex.dd_add(hi, lo, ph, pe)
    # --- exact inverse power-of-two scaling (folds in 2^S) ---
    if out_dd:
        return hi * rr * cc, lo * rr * cc
    return ((hi + lo) * rr) * cc


def _kernel(e_ref, r1_ref, r2_ref, c1_ref, c2_ref, out_ref, *, ctx, out_dd):
    planes = [e_ref[0, t, :, :].astype(jnp.float32) for t in range(ctx.n)]
    rr = r1_ref[...] * r2_ref[...]
    cc = c1_ref[...] * c2_ref[...]
    if out_dd:
        hi, lo = garner_tile(planes, rr, cc, ctx=ctx, out_dd=True)
        out_ref[0, 0, :, :] = hi
        out_ref[0, 1, :, :] = lo
    else:
        out_ref[0] = garner_tile(planes, rr, cc, ctx=ctx, out_dd=False)


# not jitted: CRTContext holds numpy tables and is unhashable; the public
# pipeline wrappers jit the whole plan execution anyway.
def _stacked_call(e_res, r1, r2, c1, c2, *, ctx, out_dd, bm, bn, interpret):
    s, n_mod, m, n = e_res.shape
    out_shape = (
        jax.ShapeDtypeStruct((s, 2, m, n), jnp.float32)
        if out_dd
        else jax.ShapeDtypeStruct((s, m, n), jnp.float32)
    )
    out_spec = (
        pl.BlockSpec((1, 2, bm, bn), lambda si, i, j: (si, I0, i, j))
        if out_dd
        else pl.BlockSpec((1, bm, bn), lambda si, i, j: (si, i, j))
    )
    row_spec = pl.BlockSpec((bm, 1), lambda si, i, j: (i, I0))
    col_spec = pl.BlockSpec((1, bn), lambda si, i, j: (I0, j))
    return pl.pallas_call(
        functools.partial(_kernel, ctx=ctx, out_dd=out_dd),
        grid=(s, m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((1, ctx.n, bm, bn), lambda si, i, j: (si, I0, i, j)),
            row_spec,
            row_spec,
            col_spec,
            col_spec,
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="crt_garner",
    )(e_res, r1[:, None], r2[:, None], c1[None, :], c2[None, :])


def crt_garner(
    e_res: jnp.ndarray,
    e_mu: jnp.ndarray,
    e_nu: jnp.ndarray,
    ctx: CRTContext,
    *,
    out_dd: bool = False,
    bm: int = 256,
    bn: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """e_res: (N, m, n) or stacked (S, N, m, n) int8 residues of C'; e_mu /
    e_nu: integer scale exponents (shared across the stack).  Returns
    C = C'/(mu nu) as (m,n) f32 or (2,m,n) double-single — with a leading
    (S, ...) dim for stacked input — in one `pallas_call` either way.
    """
    if interpret is None:
        interpret = interpret_default()
    stacked = e_res.ndim == 4
    if not stacked:
        e_res = e_res[None]
    _, n_mod, m, n = e_res.shape
    assert n_mod == ctx.n
    bm, mp = block_and_padded(m, bm, align=8)
    bn, np_ = block_and_padded(n, bn, align=128)
    e_res = pad_dims(e_res, {2: mp, 3: np_})
    e_mu = pad_dims(e_mu, {0: mp})
    e_nu = pad_dims(e_nu, {0: np_})
    s = _prescale(ctx)
    s_r = s // 2
    r1, r2 = split_scale_exponent(-e_mu, bias=s_r)
    c1, c2 = split_scale_exponent(-e_nu, bias=s - s_r)
    out = _stacked_call(
        e_res, r1, r2, c1, c2, ctx=ctx, out_dd=out_dd, bm=bm, bn=bn,
        interpret=bool(interpret),
    )
    out = out[..., :m, :n]
    return out if stacked else out[0]
