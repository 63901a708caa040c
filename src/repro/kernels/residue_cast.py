"""Pallas kernel: fused scale -> trunc -> limb-split -> N int8 residue planes.

Alg. 1 steps IV + V-i/ii in one pass over the input: reads the source matrix
tile once from HBM and writes all N residue planes, instead of N separate
elementwise passes (the paper's step-1 memory term `(3N + ...)k(m+n)/b` is
dominated by exactly this traffic).

Grid: (S, m/bm, k/bk) with S an optional leading *stack* dimension: a
(S, m, k) input casts S same-shaped matrices sharing one scale vector in a
single launch — the complex pipeline stacks the real and imaginary parts of
an operand so one operand costs one `pallas_call` regardless of dtype
class.  2D inputs are treated as S=1 and squeezed on return.

Block shapes: input (1, bm, bk) f32; scale factors (bm, 1) broadcast along
rows (axis=0 operand) or (1, bk) along columns (axis=1); output
(1, N, bm, bk) int8 — N is small and static, the whole stack of output
tiles lives in VMEM (N * bm * bk bytes; 13 * 256 * 512 = 1.7 MiB).
Non-block-divisible m/k are zero-padded to the block grid and sliced back
(zeros are residue-exact; the scale vectors pad with 1.0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import (
    I0,
    block_and_padded,
    interpret_default,
    pad_dims,
    residue_tiles_f32,
)


def _kernel(a_ref, s1_ref, s2_ref, out_ref, *, moduli, n_limbs):
    tiles = residue_tiles_f32(
        a_ref[0], s1_ref[...], s2_ref[...], moduli=moduli, n_limbs=n_limbs
    )
    for l in range(len(moduli)):
        out_ref[0, l, :, :] = tiles[l].astype(jnp.int8)


@functools.partial(
    jax.jit,
    static_argnames=("moduli", "n_limbs", "scale_axis", "bm", "bk", "interpret"),
)
def _stacked_call(a, scale1, scale2, *, moduli, n_limbs, scale_axis, bm, bk,
                  interpret):
    s, m, k = a.shape
    n = len(moduli)

    if scale_axis == 0:
        scale_spec = pl.BlockSpec((bm, 1), lambda si, i, j: (i, I0))
        scale1, scale2 = scale1[:, None], scale2[:, None]
    else:
        scale_spec = pl.BlockSpec((1, bk), lambda si, i, j: (I0, j))
        scale1, scale2 = scale1[None, :], scale2[None, :]
    grid = (s, m // bm, k // bk)
    return pl.pallas_call(
        functools.partial(_kernel, moduli=moduli, n_limbs=n_limbs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda si, i, j: (si, i, j)),
            scale_spec,
            scale_spec,
        ],
        out_specs=pl.BlockSpec((1, n, bm, bk), lambda si, i, j: (si, I0, i, j)),
        out_shape=jax.ShapeDtypeStruct((s, n, m, k), jnp.int8),
        interpret=interpret,
        name="residue_cast",
    )(a, scale1, scale2)


def residue_cast(
    a: jnp.ndarray,
    scale1: jnp.ndarray,
    scale2: jnp.ndarray,
    *,
    moduli: tuple[int, ...],
    n_limbs: int,
    scale_axis: int = 0,
    bm: int = 256,
    bk: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """a: (m, k) or stacked (S, m, k) f32; scale1*scale2: power-of-two
    factors along `scale_axis` (shared by all S stack entries).  Returns
    (N, m, k) — or (S, N, m, k) for stacked input — int8 symmetric residues
    of trunc(a * scale), in one `pallas_call` either way."""
    if interpret is None:
        interpret = interpret_default()
    stacked = a.ndim == 3
    if not stacked:
        a = a[None]
    _, m, k = a.shape
    bm, mp = block_and_padded(m, bm, align=8)
    bk, kp = block_and_padded(k, bk, align=128)
    a = pad_dims(a, {1: mp, 2: kp})
    spad = mp if scale_axis == 0 else kp
    scale1 = pad_dims(scale1, {0: spad}, value=1.0)
    scale2 = pad_dims(scale2, {0: spad}, value=1.0)
    out = _stacked_call(
        a, scale1, scale2, moduli=tuple(moduli), n_limbs=n_limbs,
        scale_axis=scale_axis, bm=bm, bk=bk, interpret=bool(interpret),
    )[:, :, :m, :k]
    return out if stacked else out[0]
