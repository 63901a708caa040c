r"""Pallas kernel: modulus-batched residue GEMM on the **FP8 (e4m3) engine**.

The int8 kernel (`int8_mod_gemm.py`) feeds the MXU int8 residue planes
directly; this kernel targets the FP8 variant of the Ozaki-II scheme
(arXiv:2603.10634): the multiply engine is e4m3, whose significand holds
only 4 bits, so a symmetric residue (|r| <= 127, 7 bits) is NOT exactly
representable.  The scheme therefore splits every residue into two balanced
base-16 digits

    r = 16 * hi + lo,   hi = round(r / 16),   lo = r - 16 * hi,

with |hi| <= 8 and |lo| <= 8 — every digit is a small integer with <= 4
significant bits, hence *exact* in e4m3.  One residue product becomes three
e4m3 GEMMs per plane (the cross terms share one GEMM of doubled K):

    r_a r_b = 256 (hi_a hi_b) + 16 (hi_a lo_b + lo_a hi_b) + (lo_a lo_b)
              \__ HH GEMM __/      \____ X GEMM (2k) ____/    \_ LL GEMM _/

each accumulated in f32.  Digit products are <= 64, so an f32 accumulator
stays an exact integer for k * 128 < 2^24 — the per-launch K bound
`FP8_K_CHUNK_LIMIT` (2^16), tighter than the int8 engine's 2^17 int32 bound.
The epilogue applies the **per-plane rescale**: the digit radix weights
reduced into each plane's residue ring, m4_l = sym_mod(16, p_l) and
m8_l = sym_mod(256, p_l) (derived in-kernel from the scalar-prefetched
modulus), combine the three digit sums as

    E_l = sym_mod(m8_l * sym_mod(HH) + m4_l * sym_mod(X) + sym_mod(LL), p_l)

— every step exact small-integer f32 arithmetic, so the output is the exact
canonical symmetric residue of A_l B_l and the FP8 path is **bitwise
identical** to the int8 engine (asserted in tests/test_fp8.py).  Emulation
accuracy is set by the CRT pipeline, not the engine; what the engine changes
is throughput (priced by `perfmodel` as 4 digit-MAC volumes at the e4m3
rate vs 1 at the int8 rate).

Grid and conventions mirror `int8_mod_gemm_batched`: (N, m/bm, n/bn, k/bk)
with the modulus plane outermost, moduli scalar-prefetched (static tuple or
traced int32 array — the kernel is modulus-agnostic), an optional int8
`carry` folded into the epilogue for K-chunked products, and pad-and-slice
for non-block-divisible shapes (zeros are residue-exact).

Hosts without native e4m3 matmul support run the same code in interpreted
Pallas (`interpret=None` resolves via `common.interpret_default`): the
digits are exactly representable, so XLA's upcast-and-multiply fallback is
bit-identical to a hardware fp8 MAC with f32 accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (
    block_and_padded,
    resolve_blocks,
    dyn_mod_params,
    interpret_default,
    pad_dims,
    sym_mod_f32,
    sym_mod_int32_dyn,
)

# Per-launch K bound of the f32 digit accumulators: worst-case per-element
# digit-product mass is 2 * 8 * 8 = 128 (the X GEMM sums two digit products
# per k), and f32 integer arithmetic is exact below 2^24, so k <= 2^24 / 128
# = 2^17; we keep a 2x margin.  `Fp8Backend` threads this through
# `chunked_residue_matmul` in place of the int8 engine's int32 bound.
FP8_K_CHUNK_LIMIT = 1 << 16

_F8 = jnp.float8_e4m3fn


def _digits(r32: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Balanced base-16 digit split of f32 integer residues (|r| <= 127):
    hi = round(r/16) in [-8, 8], lo = r - 16*hi in [-8, 8] — both exact in
    e4m3 (<= 4 significant bits)."""
    hi = jnp.round(r32 * (1.0 / 16.0))
    lo = r32 - 16.0 * hi
    return hi, lo


def _kernel(moduli_ref, a_ref, b_ref, *rest, k_steps, has_carry):
    if has_carry:
        carry_ref, out_ref, hh_ref, xx_ref, ll_ref = rest
    else:
        out_ref, hh_ref, xx_ref, ll_ref = rest
    # program_id must be read outside pl.when bodies (the interpret-mode
    # evaluator does not substitute it inside cond sub-jaxprs)
    l = pl.program_id(0)

    @pl.when(pl.program_id(3) == 0)
    def _init():
        hh_ref[...] = jnp.zeros_like(hh_ref)
        xx_ref[...] = jnp.zeros_like(xx_ref)
        ll_ref[...] = jnp.zeros_like(ll_ref)

    ah, al = _digits(a_ref[0].astype(jnp.float32))
    bh, bl = _digits(b_ref[0].astype(jnp.float32))
    # round through e4m3: exact (digits have <= 4 significant bits), and the
    # dot then runs on genuine fp8 operands — the MXU fp8 path on hardware
    # that has one, XLA's upcast fallback (bit-identical) elsewhere
    dot = functools.partial(
        jax.lax.dot_general,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    hh_ref[...] += dot(ah.astype(_F8), bh.astype(_F8))
    ll_ref[...] += dot(al.astype(_F8), bl.astype(_F8))
    # cross terms as ONE fp8 GEMM of doubled K: [ah | al] @ [bl ; bh]
    xx_ref[...] += dot(
        jnp.concatenate([ah, al], axis=1).astype(_F8),
        jnp.concatenate([bl, bh], axis=0).astype(_F8),
    )

    @pl.when(pl.program_id(3) == k_steps - 1)
    def _epilogue():
        pf, half, m16 = dyn_mod_params(moduli_ref, l)
        # per-plane rescale constants: the digit radix in the residue ring
        m4 = sym_mod_f32(jnp.float32(16.0), pf, half)
        m8 = sym_mod_f32(m4 * m4, pf, half)  # 256 mod p == (16 mod p)^2 mod p
        # f32 digit sums are exact integers < 2^24: int32 conversion is exact
        # and the 16-bit-split reduction gives the exact symmetric residue
        eh = sym_mod_int32_dyn(hh_ref[...].astype(jnp.int32), pf, half, m16)
        ex = sym_mod_int32_dyn(xx_ref[...].astype(jnp.int32), pf, half, m16)
        el = sym_mod_int32_dyn(ll_ref[...].astype(jnp.int32), pf, half, m16)
        acc = m8 * eh + m4 * ex + el  # |.| <= 2*127^2 + 127 < 2^16: exact
        if has_carry:
            acc = acc + carry_ref[0].astype(jnp.float32)
        out_ref[0] = sym_mod_f32(acc, pf, half).astype(jnp.int8)


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
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps, has_carry=carry is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_mod, m, n), jnp.int8),
        interpret=interpret,
        name="fp8_mod_gemm",
    )(mod_arr, *operands)


def _karatsuba_kernel(moduli_ref, ar_ref, ai_ref, br_ref, bi_ref, *rest,
                      k_steps, has_carry):
    if has_carry:
        (cr_in_ref, ci_in_ref, cr_ref, ci_ref, *accs) = rest
    else:
        (cr_ref, ci_ref, *accs) = rest
    d_hh, d_xx, d_ll, e_hh, e_xx, e_ll, f_hh, f_xx, f_ll = accs
    # program_id read once at kernel top level (outside pl.when bodies)
    pf, half, m16 = dyn_mod_params(moduli_ref, pl.program_id(0))

    @pl.when(pl.program_id(3) == 0)
    def _init():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    ar = ar_ref[0].astype(jnp.float32)
    ai = ai_ref[0].astype(jnp.float32)
    br = br_ref[0].astype(jnp.float32)
    bi = bi_ref[0].astype(jnp.float32)
    # (AR + AI) mod p formed in VMEM: |sum| <= 254 -> exact f32 mod
    asum = sym_mod_f32(ar + ai, pf, half)
    bsum = sym_mod_f32(br + bi, pf, half)
    dot = functools.partial(
        jax.lax.dot_general,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    def accumulate(a32, b32, hh, xx, ll):
        ah, al = _digits(a32)
        bh, bl = _digits(b32)
        hh[...] += dot(ah.astype(_F8), bh.astype(_F8))
        ll[...] += dot(al.astype(_F8), bl.astype(_F8))
        xx[...] += dot(
            jnp.concatenate([ah, al], axis=1).astype(_F8),
            jnp.concatenate([bl, bh], axis=0).astype(_F8),
        )

    accumulate(ar, br, d_hh, d_xx, d_ll)
    accumulate(ai, bi, e_hh, e_xx, e_ll)
    accumulate(asum, bsum, f_hh, f_xx, f_ll)

    @pl.when(pl.program_id(3) == k_steps - 1)
    def _epilogue():
        m4 = sym_mod_f32(jnp.float32(16.0), pf, half)
        m8 = sym_mod_f32(m4 * m4, pf, half)

        def combine(hh, xx, ll):
            eh = sym_mod_int32_dyn(hh[...].astype(jnp.int32), pf, half, m16)
            exx = sym_mod_int32_dyn(xx[...].astype(jnp.int32), pf, half, m16)
            el = sym_mod_int32_dyn(ll[...].astype(jnp.int32), pf, half, m16)
            return sym_mod_f32(m8 * eh + m4 * exx + el, pf, half)

        dr = combine(d_hh, d_xx, d_ll)
        de = combine(e_hh, e_xx, e_ll)
        df = combine(f_hh, f_xx, f_ll)
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
def _karatsuba_call(ar, ai, br, bi, carry, mod_arr, *, bm, bn, bk, interpret):
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
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)] * 9,
    )
    return pl.pallas_call(
        functools.partial(
            _karatsuba_kernel, k_steps=k_steps, has_carry=carry is not None
        ),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((n_mod, m, n), jnp.int8),
            jax.ShapeDtypeStruct((n_mod, m, n), jnp.int8),
        ),
        interpret=interpret,
        name="fp8_karatsuba_mod_gemm",
    )(mod_arr, *operands)


def fp8_karatsuba_mod_gemm_batched(
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
    """Residues of (CR', CI') = (AR'+iAI')(BR'+iBI') mod p_l on the e4m3
    engine, all planes and all three Karatsuba products in ONE launch.

    The fp8 twin of `karatsuba_mod_gemm_batched`: the D/E/F products each
    run as the exact balanced-digit HH/X/LL triple (9 f32 accumulators in
    VMEM), the (AR+AI)/(BR+BI) sum operands are formed per tile in VMEM, and
    the epilogue combines digits and the Karatsuba recombination in exact
    f32 — bitwise identical to composing three `fp8_mod_gemm_batched` calls
    with host combines, in 1 launch instead of 3.  Inputs (N, m, k) /
    (N, k, n) int8 stacks, optional (CR, CI) carry pair, k <=
    `FP8_K_CHUNK_LIMIT` per launch.
    """
    if interpret is None:
        interpret = interpret_default()
    n_mod, m, k = ar.shape
    if k > FP8_K_CHUNK_LIMIT:
        raise ValueError(
            f"fp8 digit accumulation is exact only for k <= "
            f"{FP8_K_CHUNK_LIMIT} per launch (got k={k}); chunk via "
            f"chunked_residue_matmul(chunk_limit=FP8_K_CHUNK_LIMIT)"
        )
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
    bm, bn, bk = resolve_blocks("fp8", "complex", m, n, k, bm, bn, bk)
    bm, mp = block_and_padded(m, bm, align=128)
    bn, np_ = block_and_padded(n, bn, align=128)
    bk, kp = block_and_padded(k, bk, align=128)
    ar = pad_dims(ar, {1: mp, 2: kp})
    ai = pad_dims(ai, {1: mp, 2: kp})
    br = pad_dims(br, {1: kp, 2: np_})
    bi = pad_dims(bi, {1: kp, 2: np_})
    if carry is not None:
        carry = tuple(pad_dims(c, {1: mp, 2: np_}) for c in carry)
    cr, ci = _karatsuba_call(
        ar, ai, br, bi, carry, jnp.asarray(moduli, jnp.int32),
        bm=bm, bn=bn, bk=bk, interpret=bool(interpret),
    )
    return cr[:, :m, :n], ci[:, :m, :n]


def fp8_mod_gemm_batched(
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
    """E_l = sym_mod(A_l @ B_l [+ carry_l], p_l) on the e4m3 engine, all N
    planes in ONE launch.

    a: (N, m, k) int8, b: (N, k, n) int8, carry: optional (N, m, n) int8;
    returns (N, m, n) int8 residues, bitwise identical to
    `int8_mod_gemm_batched` (the digit split and per-plane rescale are
    exact — see module docstring).  Any m/n/k up to `FP8_K_CHUNK_LIMIT` per
    launch is accepted (pad-and-slice); `moduli` may be a static tuple or a
    traced (N,) int32 array.
    """
    if interpret is None:
        interpret = interpret_default()
    n_mod, m, k = a.shape
    if k > FP8_K_CHUNK_LIMIT:
        raise ValueError(
            f"fp8 digit accumulation is exact only for k <= "
            f"{FP8_K_CHUNK_LIMIT} per launch (got k={k}); chunk via "
            f"chunked_residue_matmul(chunk_limit=FP8_K_CHUNK_LIMIT)"
        )
    n_given = (
        moduli.shape[0] if isinstance(moduli, jnp.ndarray) else len(moduli)
    )
    if b.shape[0] != n_mod or b.shape[1] != k or n_given != n_mod:
        raise ValueError(f"shape mismatch: a {a.shape}, b {b.shape}, N={n_given}")
    n = b.shape[-1]
    bm, bn, bk = resolve_blocks("fp8", "real", m, n, k, bm, bn, bk)
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
