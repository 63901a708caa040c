"""Pallas kernel: modulus-batched fused-Karatsuba modular complex GEMM.

Beyond-paper optimization (EXPERIMENTS.md SPerf), two fusions deep:

 1. *Karatsuba fusion* — the paper runs the three Karatsuba products
    D = AR.BR, E = AI.BI, F = (AR+AI)(BR+BI) as separate int8 GEMM kernel
    launches with int32 intermediates in HBM.  We fuse all three into one
    kernel that reads only the 4 residue planes (the (AR+AI) mod p and
    (BR+BI) mod p operands are formed in VMEM per tile — exact f32 mod of
    values <= 254 — never materialized in HBM), keeps the three int32
    accumulators in VMEM scratch, and emits the final CR/CI int8 residues
    directly: CR = D - E, CI = F - D - E (mod p).  HBM traffic per modulus
    drops from 6 int8 plane reads + 3 int32 (m,n) writes + 3 int32 reads +
    2 int8 writes to 4 int8 reads + 2 int8 writes.
 2. *Modulus batching* — all N planes run in one `pallas_call` with the
    modulus plane as the leading grid dimension, so a full fast-mode
    complex residue product is ONE launch (vs 3N for the paper's schedule).

Grid: (N, m/bm, n/bn, k/bk) — modulus outermost, k innermost, 3 int32 VMEM
accumulators.  The per-plane modulus arrives via scalar prefetch as an
int32 array (`PrefetchScalarGridSpec`); (p, (p-1)/2, 2^16 mod p) are
derived in-kernel in exact f32 (`common.dyn_mod_params`).  Alignment: bm/bn
and bk multiples of 128 (bk is the A block's lane dimension) unless the
axis fits one block; non-block-divisible shapes are
zero-padded to the block grid and sliced back (zero padding is
residue-exact).  The optional `carry` pair (CR, CI residues of previous
K-chunks) is folded into the epilogue mod, keeping chunked-K combines
inside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (
    DEFAULT_SCOPED_VMEM,
    FUSED_VMEM_LIMIT,
    I0,
    block_and_padded,
    dyn_mod_params,
    interpret_default,
    pad_dims,
    product_vmem_bytes,
    residue_tiles_f32,
    resolve_blocks,
    split_scale_exponent,
    static_mod_params,
    sym_mod_f32,
    sym_mod_int32_dyn,
)
from .crt_garner import _prescale, garner_tile


def _dot_i8(a, b):
    return jax.lax.dot_general(
        a, b, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def _kernel(moduli_ref, ar_ref, ai_ref, br_ref, bi_ref, *rest,
            k_steps, has_carry):
    if has_carry:
        cr_in_ref, ci_in_ref, cr_ref, ci_ref, d_acc, e_acc, f_acc = rest
    else:
        cr_ref, ci_ref, d_acc, e_acc, f_acc = rest
    # program_id read once at kernel top level (outside pl.when bodies —
    # the interpret-mode evaluator does not substitute it inside conds)
    pf, half, m16 = dyn_mod_params(moduli_ref, pl.program_id(0))

    @pl.when(pl.program_id(3) == 0)
    def _init():
        d_acc[...] = jnp.zeros_like(d_acc)
        e_acc[...] = jnp.zeros_like(e_acc)
        f_acc[...] = jnp.zeros_like(f_acc)

    ar, ai = ar_ref[0], ai_ref[0]
    br, bi = br_ref[0], bi_ref[0]
    # (AR + AI) mod p formed in VMEM: |sum| <= 254 -> exact f32 mod -> int8
    asum = sym_mod_f32(ar.astype(jnp.float32) + ai.astype(jnp.float32), pf, half
                       ).astype(jnp.int8)
    bsum = sym_mod_f32(br.astype(jnp.float32) + bi.astype(jnp.float32), pf, half
                       ).astype(jnp.int8)
    d_acc[...] += _dot_i8(ar, br)
    e_acc[...] += _dot_i8(ai, bi)
    f_acc[...] += _dot_i8(asum, bsum)

    @pl.when(pl.program_id(3) == k_steps - 1)
    def _epilogue():
        dr = sym_mod_int32_dyn(d_acc[...], pf, half, m16)
        de = sym_mod_int32_dyn(e_acc[...], pf, half, m16)
        df = sym_mod_int32_dyn(f_acc[...], pf, half, m16)
        cr = dr - de
        ci = df - dr - de
        if has_carry:
            cr = cr + cr_in_ref[0].astype(jnp.float32)
            ci = ci + ci_in_ref[0].astype(jnp.float32)
        cr_ref[0] = sym_mod_f32(cr, pf, half).astype(jnp.int8)
        ci_ref[0] = sym_mod_f32(ci, pf, half).astype(jnp.int8)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret")
)
def _batched_call(ar, ai, br, bi, carry, mod_arr, *, bm, bn, bk, interpret):
    n_mod, m, k = ar.shape
    n = br.shape[-1]
    k_steps = k // bk
    a_spec = pl.BlockSpec((1, bm, bk), lambda l, i, j, kk, mods: (l, i, kk))
    b_spec = pl.BlockSpec((1, bk, bn), lambda l, i, j, kk, mods: (l, kk, j))
    o_spec = pl.BlockSpec((1, bm, bn), lambda l, i, j, kk, mods: (l, i, j))
    in_specs = [a_spec, a_spec, b_spec, b_spec]
    operands = [ar, ai, br, bi]
    if carry is not None:
        in_specs += [o_spec, o_spec]
        operands += list(carry)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_mod, m // bm, n // bn, k_steps),
        in_specs=in_specs,
        out_specs=(o_spec, o_spec),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.int32),
            pltpu.VMEM((bm, bn), jnp.int32),
            pltpu.VMEM((bm, bn), jnp.int32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps, has_carry=carry is not None),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((n_mod, m, n), jnp.int8),
            jax.ShapeDtypeStruct((n_mod, m, n), jnp.int8),
        ),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=max(
            DEFAULT_SCOPED_VMEM, product_vmem_bytes(bm, bn, bk) + (4 << 20))),
        interpret=interpret,
        name="karatsuba_mod_gemm",
    )(mod_arr, *operands)


def karatsuba_mod_gemm_batched(
    ar: jnp.ndarray,
    ai: jnp.ndarray,
    br: jnp.ndarray,
    bi: jnp.ndarray,
    *,
    moduli: tuple[int, ...] | jnp.ndarray,
    carry: tuple[jnp.ndarray, jnp.ndarray] | None = None,
    bm: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    interpret: bool | None = None,
):
    """Residues of (CR', CI') = (AR'+iAI')(BR'+iBI') mod p_l, all planes in
    ONE launch.  Inputs (N, m, k) / (N, k, n) int8 stacks; `carry` is an
    optional (CR, CI) pair of (N, m, n) int8 residues folded into the
    epilogue (K-chunk combine).  Any m/n/k is accepted (pad-and-slice).
    `moduli` may be a static tuple or a traced (N,) int32 array (the sharded
    execution's per-shard plane chunk) — the kernel is modulus-agnostic."""
    if interpret is None:
        interpret = interpret_default()
    n_mod, m, k = ar.shape
    n_given = (
        moduli.shape[0] if isinstance(moduli, jnp.ndarray) else len(moduli)
    )
    if (
        ai.shape != ar.shape
        or br.shape != bi.shape
        or br.shape[:2] != (n_mod, k)
        or n_given != n_mod
    ):
        raise ValueError(
            f"shape mismatch: ar {ar.shape}, ai {ai.shape}, br {br.shape}, "
            f"bi {bi.shape}, N={n_given}"
        )
    n = br.shape[-1]
    bm, bn, bk = resolve_blocks("kernel", "complex", m, n, k, bm, bn, bk)
    bm, mp = block_and_padded(m, bm, align=128)
    bn, np_ = block_and_padded(n, bn, align=128)
    bk, kp = block_and_padded(k, bk, align=128)
    ar = pad_dims(ar, {1: mp, 2: kp})
    ai = pad_dims(ai, {1: mp, 2: kp})
    br = pad_dims(br, {1: kp, 2: np_})
    bi = pad_dims(bi, {1: kp, 2: np_})
    if carry is not None:
        carry = tuple(pad_dims(c, {1: mp, 2: np_}) for c in carry)
    cr, ci = _batched_call(
        ar, ai, br, bi, carry, jnp.asarray(moduli, jnp.int32),
        bm=bm, bn=bn, bk=bk, interpret=bool(interpret),
    )
    return cr[:, :m, :n], ci[:, :m, :n]


# --------------------------------------------------------------- megakernel


def _fused_kernel(
    *refs, ctx, n_limbs, k_steps, chunk_steps, out_dd, prepared
):
    """cast AR/AI (+BR/BI) + 3 Karatsuba products x N planes + two Garner
    reconstructions, one grid — the complex twin of
    `int8_mod_gemm._fused_kernel` (same shared prologue/epilogue helpers,
    same bitwise-parity-by-construction argument)."""
    if prepared:
        (ar_ref, ai_ref, sa1_ref, sa2_ref, brr_ref, bri_ref,
         r1_ref, r2_ref, c1_ref, c2_ref,
         cr_ref, ci_ref, d_acc, e_acc, f_acc) = refs
    else:
        (ar_ref, ai_ref, sa1_ref, sa2_ref, br_ref, bi_ref,
         sb1_ref, sb2_ref, r1_ref, r2_ref, c1_ref, c2_ref,
         cr_ref, ci_ref, d_acc, e_acc, f_acc) = refs
    n = ctx.n
    # program_id read once at kernel top level (outside pl.when bodies)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        d_acc[...] = jnp.zeros_like(d_acc)
        e_acc[...] = jnp.zeros_like(e_acc)
        f_acc[...] = jnp.zeros_like(f_acc)

    # --- prologue: in-kernel residue casts (f32 canonical residue tiles) ---
    sa1, sa2 = sa1_ref[...], sa2_ref[...]
    art = residue_tiles_f32(
        ar_ref[...], sa1, sa2, moduli=ctx.moduli, n_limbs=n_limbs
    )
    ait = residue_tiles_f32(
        ai_ref[...], sa1, sa2, moduli=ctx.moduli, n_limbs=n_limbs
    )
    if prepared:
        brt = [brr_ref[l].astype(jnp.float32) for l in range(n)]
        bit = [bri_ref[l].astype(jnp.float32) for l in range(n)]
    else:
        sb1, sb2 = sb1_ref[...], sb2_ref[...]
        brt = residue_tiles_f32(
            br_ref[...], sb1, sb2, moduli=ctx.moduli, n_limbs=n_limbs
        )
        bit = residue_tiles_f32(
            bi_ref[...], sb1, sb2, moduli=ctx.moduli, n_limbs=n_limbs
        )

    # --- the D/E/F Karatsuba triple per plane (sum operands in VMEM) ---
    for l, p in enumerate(ctx.moduli):
        pf, half = float(p), float((p - 1) // 2)
        asum = sym_mod_f32(art[l] + ait[l], pf, half).astype(jnp.int8)
        bsum = sym_mod_f32(brt[l] + bit[l], pf, half).astype(jnp.int8)
        d_acc[l] += _dot_i8(art[l].astype(jnp.int8), brt[l].astype(jnp.int8))
        e_acc[l] += _dot_i8(ait[l].astype(jnp.int8), bit[l].astype(jnp.int8))
        f_acc[l] += _dot_i8(asum, bsum)

    # --- in-kernel K-chunk reduction (replaces the host carry loop) ---
    if k_steps > chunk_steps:

        @pl.when(((kk + 1) % chunk_steps == 0) & (kk < k_steps - 1))
        def _chunk_reduce():
            for l, p in enumerate(ctx.moduli):
                pf, half, m16 = static_mod_params(p)
                for acc in (d_acc, e_acc, f_acc):
                    acc[l] = sym_mod_int32_dyn(
                        acc[l], pf, half, m16
                    ).astype(jnp.int32)

    # --- epilogue: CR/CI combine + two Garner reconstructions ---
    @pl.when(kk == k_steps - 1)
    def _epilogue():
        cr_planes, ci_planes = [], []
        for l, p in enumerate(ctx.moduli):
            pf, half, m16 = static_mod_params(p)
            dr = sym_mod_int32_dyn(d_acc[l], pf, half, m16)
            de = sym_mod_int32_dyn(e_acc[l], pf, half, m16)
            df = sym_mod_int32_dyn(f_acc[l], pf, half, m16)
            cr_planes.append(sym_mod_f32(dr - de, pf, half))
            ci_planes.append(sym_mod_f32(df - dr - de, pf, half))
        rr = r1_ref[...] * r2_ref[...]
        cc = c1_ref[...] * c2_ref[...]
        if out_dd:
            hi, lo = garner_tile(cr_planes, rr, cc, ctx=ctx, out_dd=True)
            cr_ref[0], cr_ref[1] = hi, lo
            hi, lo = garner_tile(ci_planes, rr, cc, ctx=ctx, out_dd=True)
            ci_ref[0], ci_ref[1] = hi, lo
        else:
            cr_ref[...] = garner_tile(cr_planes, rr, cc, ctx=ctx, out_dd=False)
            ci_ref[...] = garner_tile(ci_planes, rr, cc, ctx=ctx, out_dd=False)


# not jitted: CRTContext holds numpy tables and is unhashable; the public
# pipeline wrappers jit the whole plan execution anyway.
def _fused_call(
    ar, ai, sa1, sa2, b_pair, sb, r1, r2, c1, c2, *, ctx, n_limbs, k_steps,
    chunk_steps, out_dd, bm, bn, bk, interpret
):
    prepared = sb is None
    m = ar.shape[0]
    n = b_pair[0].shape[-1]
    # scale vectors travel as (m, 1) rows / (1, n) columns (`split_scale_exponent`)
    row_spec = pl.BlockSpec((bm, 1), lambda i, j, kk: (i, I0))
    col_spec = pl.BlockSpec((1, bn), lambda i, j, kk: (I0, j))
    a_spec = pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))
    in_specs = [a_spec, a_spec, row_spec, row_spec]
    operands = [ar, ai, sa1[:, None], sa2[:, None]]
    if prepared:
        bp_spec = pl.BlockSpec((ctx.n, bk, bn), lambda i, j, kk: (I0, kk, j))
        in_specs += [bp_spec, bp_spec]
        operands += list(b_pair)
    else:
        b_spec = pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))
        in_specs += [b_spec, b_spec, col_spec, col_spec]
        operands += list(b_pair) + [sb[0][None, :], sb[1][None, :]]
    in_specs += [row_spec, row_spec, col_spec, col_spec]
    operands += [r1[:, None], r2[:, None], c1[None, :], c2[None, :]]
    one_shape = (
        jax.ShapeDtypeStruct((2, m, n), jnp.float32)
        if out_dd
        else jax.ShapeDtypeStruct((m, n), jnp.float32)
    )
    one_spec = (
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
        out_specs=(one_spec, one_spec),
        out_shape=(one_shape, one_shape),
        scratch_shapes=[
            pltpu.VMEM((ctx.n, bm, bn), jnp.int32),
            pltpu.VMEM((ctx.n, bm, bn), jnp.int32),
            pltpu.VMEM((ctx.n, bm, bn), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=FUSED_VMEM_LIMIT),
        interpret=interpret,
        name="fused_karatsuba_mod_gemm",
    )(*operands)


def fused_karatsuba_mod_gemm(
    ar: jnp.ndarray,
    ai: jnp.ndarray,
    br: jnp.ndarray,
    bi: jnp.ndarray,
    e_mu: jnp.ndarray,
    e_nu: jnp.ndarray,
    ctx,
    *,
    n_limbs: int,
    out_dd: bool = False,
    b_res: tuple[jnp.ndarray, jnp.ndarray] | None = None,
    bm: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    chunk_limit: int | None = None,
    interpret: bool | None = None,
):
    """The one-launch complex megakernel: C = (AR+iAI)(BR+iBI) emulated.

    ar/ai: (m, k) f32 pre-scaled mantissas; br/bi: (k, n) f32, or None with
    `b_res` the pre-cast ((N, k, n), (N, k, n)) int8 plane pair (prepared
    serving).  Returns the reconstructed (cr, ci) pair, each (m, n) f32 —
    or (2, m, n) double-single with `out_dd` — in ONE `pallas_call`:
    residue casts in the prologue, the fused Karatsuba D/E/F triple per K
    block (in-kernel chunk reduction past `chunk_limit` columns), CR/CI
    combine + both Garner reconstructions in the epilogue.  Bitwise
    identical to the composed cast/karatsuba/reconstruct kernel path.
    """
    if interpret is None:
        interpret = interpret_default()
    if chunk_limit is None:
        chunk_limit = 1 << 17
    ar = ar.astype(jnp.float32)
    ai = ai.astype(jnp.float32)
    m, k = ar.shape
    n = b_res[0].shape[-1] if b_res is not None else br.shape[-1]
    bm, bn, bk = resolve_blocks("fused", "complex", m, n, k, bm, bn, bk)
    bm, mp = block_and_padded(m, bm, align=128)
    bn, np_ = block_and_padded(n, bn, align=128)
    bk, kp = block_and_padded(k, bk, align=128)
    ar = pad_dims(ar, {0: mp, 1: kp})
    ai = pad_dims(ai, {0: mp, 1: kp})
    e_mu = pad_dims(e_mu, {0: mp})
    e_nu = pad_dims(e_nu, {0: np_})
    sa1, sa2 = split_scale_exponent(e_mu)
    s = _prescale(ctx)
    s_r = s // 2
    r1, r2 = split_scale_exponent(-e_mu, bias=s_r)
    c1, c2 = split_scale_exponent(-e_nu, bias=s - s_r)
    if b_res is not None:
        b_pair = tuple(pad_dims(x, {1: kp, 2: np_}) for x in b_res)
        sb = None
    else:
        b_pair = tuple(
            pad_dims(x.astype(jnp.float32), {0: kp, 1: np_}) for x in (br, bi)
        )
        sb = split_scale_exponent(e_nu)
    k_steps = kp // bk
    chunk_steps = max(1, chunk_limit // bk)
    cr, ci = _fused_call(
        ar, ai, sa1, sa2, b_pair, sb, r1, r2, c1, c2, ctx=ctx,
        n_limbs=n_limbs, k_steps=k_steps, chunk_steps=chunk_steps,
        out_dd=out_dd, bm=bm, bn=bn, bk=bk, interpret=bool(interpret),
    )
    return cr[..., :m, :n], ci[..., :m, :n]


def karatsuba_mod_gemm(
    ar: jnp.ndarray,
    ai: jnp.ndarray,
    br: jnp.ndarray,
    bi: jnp.ndarray,
    *,
    p: int,
    bm: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    interpret: bool | None = None,
):
    """Residues of (CR', CI') = (AR'+iAI')(BR'+iBI') mod p. All int8 (m,k)/(k,n).

    Per-modulus entry point, retained as a thin vmap-free wrapper over the
    batched kernel (an N=1 grid) for the reference/parity tests."""
    cr, ci = karatsuba_mod_gemm_batched(
        ar[None], ai[None], br[None], bi[None], moduli=(int(p),),
        bm=bm, bn=bn, bk=bk, interpret=interpret,
    )
    return cr[0], ci[0]
