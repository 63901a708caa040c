"""Pallas kernel: modulus-batched tiled int8 MXU GEMM with sym-mod epilogue.

Alg. 1 steps V-iii/iv for ALL moduli in one `pallas_call`: the N residue
planes are folded into the leading grid dimension, so a full residue GEMM
D_l = A_l B_l, E_l = sym_mod(D_l, p_l) costs one kernel launch regardless of
N — the paper's SIII-C step-2 launch term drops from N to 1 (on small
shapes the launch-bound regime of Fig. 1).  The int8 x int8 -> int32 MXU
product is exact for k <= 2^17 and the fused epilogue keeps the int32 tile
in VMEM (never round-trips to HBM — 8 of the 14 bytes/elt of the paper's
(14N + c) mn / b step-2 memory term; see EXPERIMENTS.md SPerf).

Grid: (N, m/bm, n/bn, k/bk) — modulus plane outermost, k innermost
('arbitrary'), one int32 accumulator tile in VMEM scratch.  The per-plane
modulus is delivered via scalar prefetch (`PrefetchScalarGridSpec`): the
moduli are a small int32 array argument, not a static Python `p`, and the
epilogue derives (p, (p-1)/2, 2^16 mod p) from it in exact f32 arithmetic
(`common.dyn_mod_params`).  MXU alignment: bm/bn/bk multiples of 128 (bk
is the lane dimension of the A block, which the TPU tiles by 128) unless
the axis fits one block; non-block-divisible shapes are
zero-padded to the block grid and the output sliced back (zeros are
residue-exact, see `common.pad_dims`).

The optional `carry` input is an (N, m, n) int8 residue stack folded into
the epilogue reduction: `out = sym_mod(acc + carry, p)`.  K-chunked
products (k > 2^17) thread the previous chunk's residues through it, so the
inter-chunk combine happens inside the kernel instead of a host-side
per-modulus loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (
    FUSED_VMEM_LIMIT,
    I0,
    block_and_padded,
    dyn_mod_params,
    interpret_default,
    pad_dims,
    residue_tiles_f32,
    resolve_blocks,
    split_scale_exponent,
    static_mod_params,
    sym_mod_int32_dyn,
)
from .crt_garner import _prescale, garner_tile


def _kernel(moduli_ref, a_ref, b_ref, *rest, k_steps, has_carry):
    if has_carry:
        carry_ref, out_ref, acc_ref = rest
    else:
        out_ref, acc_ref = rest
    # program_id must be read outside pl.when bodies (the interpret-mode
    # evaluator does not substitute it inside cond sub-jaxprs)
    l = pl.program_id(0)

    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[0],
        b_ref[0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(3) == k_steps - 1)
    def _epilogue():
        pf, half, m16 = dyn_mod_params(moduli_ref, l)
        acc = acc_ref[...]
        if has_carry:
            acc = acc + carry_ref[0].astype(jnp.int32)
        out_ref[0] = sym_mod_int32_dyn(acc, pf, half, m16).astype(jnp.int8)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret")
)
def _batched_call(a, b, carry, mod_arr, *, bm, bn, bk, interpret):
    n_mod, m, k = a.shape
    n = b.shape[-1]
    k_steps = k // bk
    in_specs = [
        pl.BlockSpec((1, bm, bk), lambda l, i, j, kk, mods: (l, i, kk)),
        pl.BlockSpec((1, bk, bn), lambda l, i, j, kk, mods: (l, kk, j)),
    ]
    operands = [a, b]
    if carry is not None:
        in_specs.append(
            pl.BlockSpec((1, bm, bn), lambda l, i, j, kk, mods: (l, i, j))
        )
        operands.append(carry)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_mod, m // bm, n // bn, k_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn), lambda l, i, j, kk, mods: (l, i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps, has_carry=carry is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_mod, m, n), jnp.int8),
        interpret=interpret,
        name="int8_mod_gemm",
    )(mod_arr, *operands)


def int8_mod_gemm_batched(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    moduli: tuple[int, ...] | jnp.ndarray,
    carry: jnp.ndarray | None = None,
    bm: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """E_l = sym_mod(A_l @ B_l [+ carry_l], p_l) for all l in ONE launch.

    a: (N, m, k) int8, b: (N, k, n) int8, carry: optional (N, m, n) int8;
    returns (N, m, n) int8 residues.  Any m/n/k is accepted (pad-and-slice).
    Unset bm/bn/bk resolve via `common.resolve_blocks`: the active
    calibration's autotuned tile for this shape bucket, else (256, 256, 512).

    `moduli` may be a static tuple or a *traced* (N,) int32 array: the
    kernel reads the modulus from the scalar-prefetched array either way
    (`dyn_mod_params`), so the compiled kernel is modulus-agnostic — the
    sharded execution passes each shard its dynamically-sliced plane chunk.
    """
    if interpret is None:
        interpret = interpret_default()
    n_mod, m, k = a.shape
    n_given = (
        moduli.shape[0] if isinstance(moduli, jnp.ndarray) else len(moduli)
    )
    if b.shape[0] != n_mod or b.shape[1] != k or n_given != n_mod:
        raise ValueError(f"shape mismatch: a {a.shape}, b {b.shape}, N={n_given}")
    n = b.shape[-1]
    bm, bn, bk = resolve_blocks("kernel", "real", m, n, k, bm, bn, bk)
    bm, mp = block_and_padded(m, bm, align=128)
    bn, np_ = block_and_padded(n, bn, align=128)
    bk, kp = block_and_padded(k, bk, align=128)
    a = pad_dims(a, {1: mp, 2: kp})
    b = pad_dims(b, {1: kp, 2: np_})
    if carry is not None:
        carry = pad_dims(carry, {1: mp, 2: np_})
    out = _batched_call(
        a, b, carry, jnp.asarray(moduli, jnp.int32), bm=bm, bn=bn, bk=bk,
        interpret=bool(interpret),
    )
    return out[:, :m, :n]


# --------------------------------------------------------------- megakernel


def _fused_kernel(
    *refs, ctx, n_limbs, k_steps, chunk_steps, out_dd, prepared
):
    """cast A tile + cast/load B tile + N int8 products + Garner, one grid.

    The prologue runs `common.residue_tiles_f32` (the residue-cast kernel's
    exact op sequence) on the raw f32 tiles; the epilogue runs
    `crt_garner.garner_tile` (the Garner kernel's exact op sequence) on the
    canonical residues — so the fused output is bitwise identical to the
    4-launch cast/cast/product/reconstruct composition by construction.
    The K grid dimension is innermost: Pallas auto-pipelines the next K
    block's fetches against the current products (the double-buffering the
    host-side chunk loop could never give across launches).
    """
    if prepared:
        (a_ref, sa1_ref, sa2_ref, b_ref,
         r1_ref, r2_ref, c1_ref, c2_ref, out_ref, acc_ref) = refs
    else:
        (a_ref, sa1_ref, sa2_ref, b_ref, sb1_ref, sb2_ref,
         r1_ref, r2_ref, c1_ref, c2_ref, out_ref, acc_ref) = refs
    n = ctx.n
    # program_id must be read outside pl.when bodies (the interpret-mode
    # evaluator does not substitute it inside cond sub-jaxprs)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # --- prologue: in-kernel residue cast of the operand tiles ---
    a_tiles = residue_tiles_f32(
        a_ref[...], sa1_ref[...], sa2_ref[...],
        moduli=ctx.moduli, n_limbs=n_limbs,
    )
    if prepared:
        b_tiles = [b_ref[l] for l in range(n)]  # pre-cast int8 planes
    else:
        b_tiles = [
            t.astype(jnp.int8)
            for t in residue_tiles_f32(
                b_ref[...], sb1_ref[...], sb2_ref[...],
                moduli=ctx.moduli, n_limbs=n_limbs,
            )
        ]

    # --- N int8 MXU products into the plane-stacked int32 accumulator ---
    for l in range(n):
        acc_ref[l] += jax.lax.dot_general(
            a_tiles[l].astype(jnp.int8),
            b_tiles[l],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )

    # --- in-kernel K-chunk reduction (replaces the host carry loop) ---
    if k_steps > chunk_steps:

        @pl.when(((kk + 1) % chunk_steps == 0) & (kk < k_steps - 1))
        def _chunk_reduce():
            for l, p in enumerate(ctx.moduli):
                pf, half, m16 = static_mod_params(p)
                acc_ref[l] = sym_mod_int32_dyn(
                    acc_ref[l], pf, half, m16
                ).astype(jnp.int32)

    # --- epilogue: Garner reconstruction of the output tile ---
    @pl.when(kk == k_steps - 1)
    def _epilogue():
        planes = []
        for l, p in enumerate(ctx.moduli):
            pf, half, m16 = static_mod_params(p)
            planes.append(sym_mod_int32_dyn(acc_ref[l], pf, half, m16))
        rr = r1_ref[...] * r2_ref[...]
        cc = c1_ref[...] * c2_ref[...]
        if out_dd:
            hi, lo = garner_tile(planes, rr, cc, ctx=ctx, out_dd=True)
            out_ref[0] = hi
            out_ref[1] = lo
        else:
            out_ref[...] = garner_tile(planes, rr, cc, ctx=ctx, out_dd=False)


# not jitted: CRTContext holds numpy tables and is unhashable; the public
# pipeline wrappers jit the whole plan execution anyway.
def _fused_call(
    a, sa1, sa2, b, sb, r1, r2, c1, c2, *, ctx, n_limbs, k_steps,
    chunk_steps, out_dd, bm, bn, bk, interpret
):
    prepared = sb is None
    m = a.shape[0]
    n = (b.shape[-1])
    # scale vectors travel as (m, 1) rows / (1, n) columns (`split_scale_exponent`)
    row_spec = pl.BlockSpec((bm, 1), lambda i, j, kk: (i, I0))
    col_spec = pl.BlockSpec((1, bn), lambda i, j, kk: (I0, j))
    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)), row_spec, row_spec]
    operands = [a, sa1[:, None], sa2[:, None]]
    if prepared:
        in_specs.append(
            pl.BlockSpec((ctx.n, bk, bn), lambda i, j, kk: (I0, kk, j))
        )
        operands.append(b)
    else:
        in_specs += [
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)), col_spec, col_spec
        ]
        operands += [b, sb[0][None, :], sb[1][None, :]]
    in_specs += [row_spec, row_spec, col_spec, col_spec]
    operands += [r1[:, None], r2[:, None], c1[None, :], c2[None, :]]
    out_shape = (
        jax.ShapeDtypeStruct((2, m, n), jnp.float32)
        if out_dd
        else jax.ShapeDtypeStruct((m, n), jnp.float32)
    )
    out_spec = (
        pl.BlockSpec((2, bm, bn), lambda i, j, kk: (I0, i, j))
        if out_dd
        else pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))
    )
    return pl.pallas_call(
        functools.partial(
            _fused_kernel, ctx=ctx, n_limbs=n_limbs, k_steps=k_steps,
            chunk_steps=chunk_steps, out_dd=out_dd, prepared=prepared,
        ),
        grid=(m // bm, n // bn, k_steps),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((ctx.n, bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=FUSED_VMEM_LIMIT),
        interpret=interpret,
        name="fused_mod_gemm",
    )(*operands)


def fused_mod_gemm(
    a: jnp.ndarray,
    b: jnp.ndarray,
    e_mu: jnp.ndarray,
    e_nu: jnp.ndarray,
    ctx,
    *,
    n_limbs: int,
    out_dd: bool = False,
    b_res: jnp.ndarray | None = None,
    bm: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    chunk_limit: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """The one-launch real megakernel: C = A @ B emulated end to end.

    a: (m, k) f32 (pre-scaled mantissas, as produced by the scaling pass);
    b: (k, n) f32, or None with `b_res` the pre-cast (N, k, n) int8 planes
    (prepared serving); e_mu/e_nu: the integer scale exponents.  Returns the
    reconstructed (m, n) f32 output — or the (2, m, n) double-single pair
    with `out_dd` — in ONE `pallas_call`: the residue casts run as the
    kernel prologue, the N int8 products accumulate per K block (with
    in-kernel chunk reduction replacing the host carry loop past
    `chunk_limit` columns), and the Garner reconstruction runs as the
    epilogue on the final K block.  Bitwise identical to the composed
    cast/product/reconstruct kernel path.
    """
    if interpret is None:
        interpret = interpret_default()
    if chunk_limit is None:
        chunk_limit = 1 << 17
    a = a.astype(jnp.float32)
    if b is not None:
        b = b.astype(jnp.float32)
    m, k = a.shape
    n = b_res.shape[-1] if b_res is not None else b.shape[-1]
    bm, bn, bk = resolve_blocks("fused", "real", m, n, k, bm, bn, bk)
    bm, mp = block_and_padded(m, bm, align=128)
    bn, np_ = block_and_padded(n, bn, align=128)
    bk, kp = block_and_padded(k, bk, align=128)
    a = pad_dims(a, {0: mp, 1: kp})
    e_mu = pad_dims(e_mu, {0: mp})
    e_nu = pad_dims(e_nu, {0: np_})
    sa1, sa2 = split_scale_exponent(e_mu)
    s = _prescale(ctx)
    s_r = s // 2
    r1, r2 = split_scale_exponent(-e_mu, bias=s_r)
    c1, c2 = split_scale_exponent(-e_nu, bias=s - s_r)
    if b_res is not None:
        bp = pad_dims(b_res, {1: kp, 2: np_})
        sb = None
    else:
        bp = pad_dims(b, {0: kp, 1: np_})
        sb = split_scale_exponent(e_nu)
    k_steps = kp // bk
    chunk_steps = max(1, chunk_limit // bk)
    out = _fused_call(
        a, sa1, sa2, bp, sb, r1, r2, c1, c2, ctx=ctx, n_limbs=n_limbs,
        k_steps=k_steps, chunk_steps=chunk_steps, out_dd=out_dd,
        bm=bm, bn=bn, bk=bk, interpret=bool(interpret),
    )
    return out[..., :m, :n]


def int8_mod_gemm(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    p: int,
    bm: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """E = sym_mod(A @ B, p): (m,k) x (k,n) int8 -> (m,n) int8 residues.

    Per-modulus entry point, retained as a thin vmap-free wrapper over the
    batched kernel (an N=1 grid): launching it once per modulus is the
    reference the batched path is verified bitwise-identical against.
    """
    return int8_mod_gemm_batched(
        a[None], b[None], moduli=(int(p),), bm=bm, bn=bn, bk=bk,
        interpret=interpret,
    )[0]
