"""Shared helpers for the Ozaki-II Pallas TPU kernels.

Everything here is exact f32/int32 arithmetic: the kernels never touch f64
(TPU has none).  Values stay within 2^23 after the limb peel, where f32
arithmetic on integers is error-free.

Two flavours of the symmetric modular reduction coexist:

  * static-p (`sym_mod_f32` with Python floats) — used where the modulus is
    a compile-time constant (residue_cast / crt_garner, whose host tables
    are per-modulus anyway);
  * dynamic-p (`dyn_mod_params` + the same `sym_mod_f32` on traced scalars)
    — used by the modulus-batched GEMM kernels, where the modulus arrives as
    a scalar-prefetched int32 array indexed by the leading grid dimension.

Both produce the exact canonical symmetric residue (the +/-1 correction
steps absorb the reciprocal rounding), so batched and per-modulus kernels
are bitwise identical.
"""
from __future__ import annotations

import jax

import jax.numpy as jnp
import numpy as np

LIMB_BITS = 24
LIMB = float(1 << LIMB_BITS)

#: a constant block index for `BlockSpec` index maps.  With x64 enabled a
#: literal 0 traces as s64, and Mosaic refuses a map that returns an s64.
I0 = np.int32(0)


#: scoped-VMEM limit of the fused megakernels.  Their prologue holds N f32
#: residue tiles per operand part at once, which outgrows the TPU's 16 MiB
#: default at the default blocks (the complex kernel needs ~17 MiB at N=7);
#: a v5e core has 128 MiB of VMEM.
FUSED_VMEM_LIMIT = 64 << 20


def interpret_default() -> bool:
    """Run kernels in interpret mode on the CPU and compiled on the TPU.

    Any other backend raises: it would run neither the compiled kernels nor
    the tested interpreter.
    """
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"the Pallas kernels run on 'tpu' (compiled) or 'cpu' "
            f"(interpret mode); the default backend is {backend!r}"
        )
    return backend == "cpu"


def sym_mod_f32(v, p, half):
    """Symmetric mod for f32 integer values |v| <= 2^23 (exact, see core).

    `p`/`half` may be Python floats (static modulus) or traced f32 scalars
    (dynamic modulus from scalar prefetch): the initial guess n = round(v/p)
    is within +/-1 of the true quotient either way, and the two correction
    steps make the result the exact canonical symmetric residue.
    """
    n = jnp.round(v * (1.0 / p))
    r = v - n * p
    r = jnp.where(r > half, r - p, r)
    r = jnp.where(r < -half, r + p, r)
    return r


def dyn_mod_params(moduli_ref, l):
    """(pf, half, m16) for plane `l` from a scalar-prefetched int32 moduli ref.

    pf = p as f32; half = (p-1)/2 (exact: p odd, so floor(p/2) == (p-1)/2);
    m16 = symmetric residue of 2^16 mod p (|m16| <= half), used by the exact
    16-bit-split int32 reduction.  All three are exact small f32 integers.
    """
    pf = moduli_ref[l].astype(jnp.float32)
    half = jnp.floor(pf * 0.5)
    m16 = sym_mod_f32(jnp.float32(float(1 << 16)), pf, half)
    return pf, half, m16


def sym_mod_int32_dyn(d, pf, half, m16):
    """Exact symmetric mod of int32 (|d| < 2^31) with a dynamic modulus.

    d = dh*2^16 + dl with dh = d >> 16 (floor), dl = d & 0xffff in [0, 2^16);
    both below 2^24 so the f32 modular arithmetic is exact.  `pf`/`half`/
    `m16` come from :func:`dyn_mod_params` (traced) or host floats (static —
    the two agree bit-for-bit because the result is the exact residue).
    """
    dh = jnp.right_shift(d, 16).astype(jnp.float32)  # arithmetic shift: floor
    dl = jnp.bitwise_and(d, (1 << 16) - 1).astype(jnp.float32)
    rh = sym_mod_f32(dh, pf, half)
    rl = sym_mod_f32(dl, pf, half)
    return sym_mod_f32(rh * m16 + rl, pf, half)


def static_mod_params(p: int) -> tuple[float, float, float]:
    """(pf, half, m16) as Python floats for a compile-time modulus.

    The static twin of :func:`dyn_mod_params`: host-computed m16 is the same
    exact symmetric residue of 2^16 mod p, so `sym_mod_int32_dyn` fed with
    these constants is bitwise identical to the dynamic-modulus call.
    """
    half = (p - 1) // 2
    m16 = pow(1 << 16, 1, p)
    if m16 > half:
        m16 -= p
    return float(p), float(half), float(m16)


def residue_tiles_f32(x, s1, s2, *, moduli, n_limbs):
    """Scale -> trunc -> limb-peel -> per-modulus canonical residues, in f32.

    The single implementation of Alg. 1 steps IV + V-i/ii shared by the
    standalone residue-cast kernel and the fused megakernel prologues: both
    run literally these ops, so their int8 planes are bitwise identical.

    `x` is one (bm, bk) f32 tile; `s1*s2` the power-of-two scale factors,
    shaped (bm, 1) for a row scale or (1, bk) for a column scale.  Returns
    a list of N (bm, bk) f32 tiles, each the exact canonical symmetric
    residue (|r| <= (p-1)/2) ready for `.astype(jnp.int8)`.
    """
    x = jnp.trunc(x * (s1 * s2))  # exact: power-of-two scale, f32 trunc

    # exact base-2^24 limb peel (DESIGN.md S2), rounding to the nearest
    # limb so every lower limb is <= 2^23 in magnitude: `sym_mod_f32` forms
    # n*p, which rounds past 2^24 unless the compiler fuses it into an FMA
    # (the CPU does, the TPU does not)
    limbs = []
    rem = x
    for i in reversed(range(1, n_limbs)):
        base = LIMB**i
        hi = jnp.round(rem * (1.0 / base))  # 1/2^24k is a power of two: exact
        rem = rem - hi * base
        limbs.append(hi)
    limbs.append(rem)
    limbs = limbs[::-1]

    radix = limb_radix_f32(moduli, n_limbs)  # static host table
    out = []
    for l, p in enumerate(moduli):
        pf, half = float(p), float((p - 1) // 2)
        acc = jnp.zeros_like(x)
        for i in range(n_limbs):
            acc = acc + sym_mod_f32(limbs[i], pf, half) * float(radix[i, l])
        out.append(sym_mod_f32(acc, pf, half))
    return out


def limb_radix_f32(moduli, n_limbs: int) -> np.ndarray:
    """(n_limbs, N) f32 table of symmetric 2^(24 i) mod p_l."""
    tab = np.zeros((n_limbs, len(moduli)), dtype=np.float32)
    for i in range(n_limbs):
        for l, p in enumerate(moduli):
            r = pow(1 << LIMB_BITS, i, p)
            if r > (p - 1) // 2:
                r -= p
            tab[i, l] = float(r)
    return tab


def split_scale_exponent(e: np.ndarray | jnp.ndarray, bias: int = 0):
    """Split exponents e+bias into two f32-safe power-of-two factors.

    Returns (s1, s2) f32 with s1*s2 == 2^(e+bias) exactly, each factor's
    exponent within f32 normal range for |e+bias| <= 252.  The kernels
    take them as 2-D (b, 1) / (1, b) blocks: a 1-D block of a scale vector
    does not match the TPU's layout of that vector.
    """
    from ..core.scaling import exp2i

    et = e + bias
    e1 = et // 2
    e2 = et - e1
    return exp2i(e1).astype(jnp.float32), exp2i(e2).astype(jnp.float32)


# ------------------------------------------------- ragged-shape pad/slice


def round_up(x: int, mult: int) -> int:
    """Smallest multiple of `mult` that is >= x."""
    return -(-x // mult) * mult


def pad_dims(x, targets: dict[int, int], value=0):
    """Zero-pad (or `value`-pad) `x` at the end of each axis up to `targets`.

    Zero padding is residue-exact: residues of 0 are 0 for every modulus,
    padded K contributes nothing to dot products, and padded M/N rows and
    columns are sliced off the output — so pad-and-slice keeps every kernel
    bit-identical on the retained region.
    """
    pads = [(0, 0)] * x.ndim
    needed = False
    for ax, tgt in targets.items():
        cur = x.shape[ax]
        if cur != tgt:
            pads[ax] = (0, tgt - cur)
            needed = True
    if not needed:
        return x
    return jnp.pad(x, pads, constant_values=value)


def block_and_padded(
    dim: int, block: int, align: int | None = None
) -> tuple[int, int]:
    """(block', padded_dim) for one axis: shrink the block to the axis when
    the axis is smaller, otherwise pick the padding-minimizing aligned block
    (perfmodel.select_block — the shared, perfmodel-visible rule) and round
    the axis up to a multiple of it.  With `align=None` (or the
    `perfmodel.BLOCK_SHRINK` knob off) this is the legacy round-up to the
    default block: just-over-a-multiple dims like m=257 then pad ~2x, which
    the aligned shrink avoids (257 @ bm=256/align=128 -> block 128, pad 384).
    """
    from ..core.perfmodel import select_block

    b = select_block(dim, block, align)
    return b, round_up(dim, b)


#: static default (bm, bn, bk) of the batched/fused GEMM kernels — what
#: runs when no calibration is active and the caller passes no blocks —
#: but the complex residue product's, which `product_tile` picks
DEFAULT_GEMM_BLOCKS = (256, 256, 512)

#: the v5e compiler's scoped-VMEM limit when a kernel sets none
DEFAULT_SCOPED_VMEM = 16 << 20
#: VMEM the complex residue product's tile may fill (a v5e core has 128 MiB)
PRODUCT_VMEM_BUDGET = 96 << 20
#: the complex residue product's largest tile: the fastest of PERF.md's
#: sweep on a v5e at m = n = k = 8192 (119.9 ms a call, against 245.4 at
#: the static blocks)
PRODUCT_TILE = (1024, 1024, 1024)


def product_vmem_bytes(bm: int, bn: int, bk: int) -> int:
    """Scoped VMEM of `karatsuba_mod_gemm_batched` at one tile.

    Per input element 8 bytes: four int8 tiles double-buffered (2), and the
    f32 temporaries of the (AR+AI) and (BR+BI) sum tiles (6).  Per output
    element 52: the two int8 output tiles and the two carry tiles
    double-buffered (8), three int32 accumulators (12), the three int32
    dot results (12) and the epilogue's f32 reductions (20).  The costs
    are fitted to the least limit at which the v5e compiler takes each
    tile of PERF.md's sweep at m = n = k = 8192 with no carry: the sum is
    at most 2 MiB under it, and `_batched_call` keeps 4 MiB to spare.
    """
    return 8 * (bm + bn) * bk + 52 * bm * bn


def product_tile(m: int, n: int, k: int) -> tuple[int, int, int]:
    """The default tile of the complex residue product at (m, n, k).

    `PRODUCT_TILE`, with bm and bn cut to a quarter of their axis in
    multiples of 128 (but not below the static 256), then shrunk to the
    axes as the kernel runs it (`block_and_padded`); the tile returned is
    that shrunk one, and its footprint fits `PRODUCT_VMEM_BUDGET`.  Larger
    tiles re-read each int8 tile, rebuild each Karatsuba sum tile and step
    the grid fewer times per MXU operation.  But the kernel's code grows
    with bm * bn and the program keeps it in HBM (on a v5e 4.8 MB more at
    1024 x 1024 than at 256 x 256, 14% of what a jitted 1024-wide cgemm
    holds), so the output keeps at least 16 tiles.  Only this kernel takes
    the tile: the real product keeps `DEFAULT_GEMM_BLOCKS` (no benchmark
    cell runs it), as do the fused megakernels, whose N x 3 accumulators
    outgrow VMEM at these tiles, and the fp8 kernels, whose budgets differ.
    """
    bm, bn, bk = PRODUCT_TILE
    bm = max(256, min(bm, m // 512 * 128))
    bn = max(256, min(bn, n // 512 * 128))
    return tuple(block_and_padded(dim, b, align=128)[0]
                 for dim, b in ((m, bm), (n, bn), (k, bk)))


def resolve_blocks(
    family: str,
    dclass: str,
    m: int,
    n: int,
    k: int,
    bm: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
) -> tuple[int, int, int]:
    """The (bm, bn, bk) a GEMM kernel launches for one (family, dclass,
    shape) slot.

    Explicit caller-passed values always win per axis.  Unset axes resolve
    from the active calibration's autotuned winner for this slot
    (`repro.tune` — `current_calibration().block_for(block_key(...))`),
    else `product_tile(m, n, k)` for the ("kernel", "complex") slot and the
    static `DEFAULT_GEMM_BLOCKS` for every other.  The result then flows through
    the exact same `block_and_padded` pad-and-slice path as the defaults,
    so tuned blocks can never change numerics — only which tiles the
    `pallas_call` grid steps over.
    """
    tuned = None
    if bm is None or bn is None or bk is None:
        # lazy import: tune.cache must stay importable without the kernels
        from ..tune.cache import block_key, current_calibration

        cal = current_calibration()
        if cal is not None:
            tuned = cal.block_for(block_key(family, dclass, m, n, k))
    if tuned:
        base = tuned
    elif (family, dclass) == ("kernel", "complex"):
        base = product_tile(m, n, k)
    else:
        base = DEFAULT_GEMM_BLOCKS
    return (
        bm if bm is not None else base[0],
        bn if bn is not None else base[1],
        bk if bk is not None else base[2],
    )


# ------------------------------------------------- launch-count diagnostics
# The jaxpr walker grew into the repro.analysis pass framework (PR 7);
# re-exported here because older callers import it from kernels.common.

from ..analysis.jaxprs import (  # noqa: E402,F401
    count_pallas_calls,
    count_pallas_launches,
    iter_subjaxprs as _iter_subjaxprs,
)


def _count_in_jaxpr(jaxpr) -> int:
    """Compat shim: pallas_call count of one (open) jaxpr, nested included."""
    from ..analysis.jaxprs import count_primitive

    return count_primitive(jaxpr, "pallas_call")
