"""CRT reconstruction (Alg. 1 steps V-v/vi) — three interchangeable paths.

paper   : the paper's eq. (5) unevaluated split S = S1 + S2 where S1 sums the
          exact high parts of w_l = (P/p_l) q_l (53-7-ceil(log2 N) bits thanks
          to the symmetric int8 residues) and S2 the rounded low parts; then
          mod(S, P) in double-double with P as an exact 3-term expansion.
dd      : full double-double accumulation of w_l * E_l (strictly more precise
          than the paper's split; used for cross-checks).
garner  : mixed-radix (Garner) reconstruction in pure small-integer
          arithmetic — the TPU-native path (no f64 on the VPU; DESIGN.md S2).
          With symmetric digits d_t in [-(p_t-1)/2,(p_t-1)/2] the representable
          range telescopes to exactly [-(P-1)/2,(P-1)/2], so uniqueness under
          condition (4) gives an *exact* integer reconstruction.

All paths take E: (N, ...) int8/int32 symmetric residues of C' and return the
value of C' as a double-double pair (hi, lo) in f64.  Inverse scaling by the
power-of-two mu, nu is exact and done by the caller.
"""
from __future__ import annotations

import functools
import math

import jax.numpy as jnp
import numpy as np

from .expansion import dd_add, dd_mul_fp, two_prod, quick_two_sum
from .moduli import CRTContext
from .residues import sym_mod_int32, sym_mod_small
from .scaling import exp2i

_F64 = jnp.float64


def reconstruct_paper(e_res: jnp.ndarray, ctx: CRTContext):
    """Paper eq. (5): S1 (exact) + S2 (low parts), then mod(S, P) in dd."""
    ef = e_res.astype(_F64)
    s1 = jnp.zeros(e_res.shape[1:], dtype=_F64)
    s2 = jnp.zeros(e_res.shape[1:], dtype=_F64)
    for l in range(ctx.n):  # fixed-order accumulation => bitwise reproducible
        s1 = s1 + float(ctx.w_hi[l]) * ef[l]
        s2 = s2 + float(ctx.w_lo[l]) * ef[l]
    return _mod_P_dd(s1, s2, ctx)


def reconstruct_dd(e_res: jnp.ndarray, ctx: CRTContext):
    """Full double-double accumulation (beyond-paper precision)."""
    ef = e_res.astype(_F64)
    hi = jnp.zeros(e_res.shape[1:], dtype=_F64)
    lo = jnp.zeros(e_res.shape[1:], dtype=_F64)
    for l in range(ctx.n):
        ph, pl = two_prod(jnp.asarray(float(ctx.w_dd_hi[l]), _F64), ef[l])
        pl = pl + float(ctx.w_dd_lo[l]) * ef[l]
        hi, lo = dd_add(hi, lo, ph, pl)
    return _mod_P_dd(hi, lo, ctx)


def _mod_P_dd(s_hi, s_lo, ctx: CRTContext):
    """mod(S, P) = S - P*round(S/P), P held as an exact 3-term expansion.

    |S/P| <= N * max|w_l| * 127 / P < 2^15, so z = round(S/P) is a small exact
    integer; each P_t * z is formed with two_prod (error-free) and subtracted
    in double-double.  This is the paper's 'simplified double-double modulo'.
    """
    z = jnp.round(s_hi / float(ctx.P))
    hi, lo = s_hi, s_lo
    for t in range(3):
        pt = float(ctx.P_exp[t])
        if pt == 0.0:
            continue
        ph, pl = two_prod(jnp.asarray(pt, _F64), z)
        hi, lo = dd_add(hi, lo, -ph, -pl)
    # one correction step in case round(S/P) was off by one.  The compare
    # runs in double-double: results within one f64 ulp of +/- P/2 would
    # otherwise compare equal to `half` and miss the correction.
    hh = float(ctx.P_exp[0]) / 2.0  # exact (power-of-two division)
    hl = (float(ctx.P_exp[1]) + float(ctx.P_exp[2])) / 2.0
    dpos_hi, dpos_lo = dd_add(hi, lo, -hh, -hl)  # result - P/2
    dneg_hi, dneg_lo = dd_add(hi, lo, hh, hl)    # result + P/2
    pos = (dpos_hi > 0) | ((dpos_hi == 0) & (dpos_lo > 0))
    neg = (dneg_hi < 0) | ((dneg_hi == 0) & (dneg_lo < 0))
    adj = jnp.where(pos, -1.0, jnp.where(neg, 1.0, 0.0))
    for t in range(3):
        pt = float(ctx.P_exp[t])
        if pt == 0.0:
            continue
        ph, pl = two_prod(jnp.asarray(pt, _F64), adj)
        hi, lo = dd_add(hi, lo, ph, pl)
    return hi, lo


def garner_digits(e_res: jnp.ndarray, ctx: CRTContext) -> jnp.ndarray:
    """Symmetric mixed-radix digits d_t, C' = sum_t d_t * prod_{s<t} p_s.

    Pure small-integer arithmetic: |(r - d_s) * inv| <= 254*254 < 2^16.
    Runs identically in int32 on TPU and on host.
    """
    e32 = e_res.astype(jnp.int32)
    digits = []
    for t in range(ctx.n):
        p_t = int(ctx.moduli_arr[t])
        half_t = int(ctx.half_arr[t])
        r = e32[t]
        for s in range(t):
            r = (r - digits[s]) * int(ctx.garner_inv[s, t])
            r = sym_mod_small(r, p_t, half_t).astype(jnp.int32)
        digits.append(r)
    return jnp.stack(digits, axis=0)


def reconstruct_garner(e_res: jnp.ndarray, ctx: CRTContext):
    """Garner digits -> double-double value (exact digits; dd conversion)."""
    digits = garner_digits(e_res, ctx)
    hi = jnp.zeros(e_res.shape[1:], dtype=_F64)
    lo = jnp.zeros(e_res.shape[1:], dtype=_F64)
    for t in range(ctx.n - 1, -1, -1):  # most-significant first
        d = digits[t].astype(_F64)
        wh, wl = float(ctx.weights_dd[t, 0]), float(ctx.weights_dd[t, 1])
        ph, pl = two_prod(jnp.asarray(wh, _F64), d)
        pl = pl + wl * d
        hi, lo = dd_add(hi, lo, ph, pl)
    return hi, lo


RECONSTRUCTORS = {
    "paper": reconstruct_paper,
    "dd": reconstruct_dd,
    "garner": reconstruct_garner,
}


def reconstruct(e_res: jnp.ndarray, ctx: CRTContext, method: str = "paper"):
    try:
        fn = RECONSTRUCTORS[method]
    except KeyError:
        raise ValueError(f"unknown reconstruction {method!r}") from None
    return fn(e_res, ctx)


def inverse_scale(hi, lo, e_mu, e_nu, out_dtype):
    """C = diag(mu)^-1 C' diag(nu)^-1 — exact (powers of two).

    The power 2^-(e_mu + e_nu) is applied as two halves: with small
    operands on both sides the whole power leaves the exponent range while
    each half, and C itself, stays within it."""
    e = -(e_mu[:, None] + e_nu[None, :])
    e1 = e // 2
    inv1, inv2 = exp2i(e1), exp2i(e - e1)
    return ((hi * inv1) * inv2 + (lo * inv1) * inv2).astype(out_dtype)


# ==================================== partial (sharded) reconstruction support
#
# A device holding only a SUBSET S of the N residue planes cannot run any of
# the reconstructors above (Garner's digit recursion is sequential over the
# moduli, and the eq. (5) low-part sum rounds order-dependently).  What it CAN
# do exactly is accumulate its planes' share of the eq. (5) linear form
#
#     S = sum_l w_l E_l,      w_l = (P/p_l) q_l  (exact Python integers)
#
# in an *unevaluated multi-part int32 split*: w_l is cut at fixed absolute
# bit positions into parts of at most 31 - 7 - ceil(log2 N) bits, so every
# product u_{j,l} * E_l and every partial/total sum of them is an exact int32
# — integer addition is associative, hence a `psum` over devices is bitwise
# order-independent on any hardware (a TPU's emulated f64 is not exact here,
# so the split does not use it).  Since w_l === delta_{li} (mod p_i), the
# full S satisfies S === E_i (mod p_i), so after the psum each device
# re-derives the COMPLETE residue planes from the exact parts in local
# small-integer arithmetic (`residues_from_partial`) and hands them to the
# ordinary reconstructor — whose output is therefore bitwise identical to
# the single-device run on the same planes, for every sharding of the
# residue dimension.


@functools.lru_cache(maxsize=None)
def partial_split(moduli: tuple[int, ...]):
    """Exact multi-part split of the eq. (5) weights for partial combines.

    Returns ``(u, radix, part_bits)``:

    * ``u``: (n_parts, N) int32 — ``u[j, l]`` is bits [j*part_bits, (j+1)*
      part_bits) of w_l, so ``w_l == sum_j u[j, l] * 2**(j*part_bits)``;
    * ``radix``: (n_parts, N) int32 — symmetric residues of
      ``2**(j*part_bits) mod p_l`` (the rebuild table);
    * ``part_bits``: the per-part width, 31 - 7 - ceil(log2 N), sized so
      ``sum_l u[j, l] * E_l`` over all N planes stays below 2^31 (|E| <= 127
      needs 7 bits, the N-term sum ceil(log2 N) more) — i.e. every partial
      sum any device or collective can form is an exact int32.
    """
    n = len(moduli)
    P = 1
    for p in moduli:
        P *= p
    ws = []
    for p in moduli:
        M = P // p
        ws.append(M * pow(M % p, -1, p))
    part_bits = 31 - 7 - max(1, math.ceil(math.log2(max(n, 2))))
    n_parts = max(1, -(-max(w.bit_length() for w in ws) // part_bits))
    u = np.zeros((n_parts, n), dtype=np.int32)
    radix = np.zeros((n_parts, n), dtype=np.int32)
    mask = (1 << part_bits) - 1
    for l, (w, p) in enumerate(zip(ws, moduli)):
        half = (p - 1) // 2
        for j in range(n_parts):
            u[j, l] = (w >> (j * part_bits)) & mask
            r = pow(2, j * part_bits, p)
            radix[j, l] = r - p if r > half else r
    return u, radix, part_bits


def partial_combine(e_res: jnp.ndarray, u: jnp.ndarray) -> jnp.ndarray:
    """(..., N_local, m, n) int8 planes -> (..., n_parts, m, n) int32 partials.

    ``u`` is this shard's (n_parts, N_local) column slice of the
    `partial_split` table (zero columns for padding planes).  Every product
    and sum is an exact int32 by the part_bits budget, so the result can be
    `psum`-reduced over the residue mesh axis bitwise order-independently.
    """
    ei = e_res.astype(jnp.int32)
    t = None
    for l in range(u.shape[1]):  # N_local multiply-adds; no integer dot
        term = u[:, l].reshape(-1, 1, 1) * ei[..., l : l + 1, :, :]
        t = term if t is None else t + term
    return t


def residues_from_partial(t_parts: jnp.ndarray, ctx: CRTContext) -> jnp.ndarray:
    """Exact int32 partial sums (n_parts, ...) -> full (N, ...) int8 residues.

    ``t_parts[j] == sum_l u[j, l] * E_l`` summed over ALL planes (i.e. after
    the psum).  Rebuilds E_i = sym_mod(sum_j t_j 2^(j*part_bits), p_i) from
    each t_j's residue and the 2^(j*part_bits) radix residue, in small exact
    int32 arithmetic.  The output equals the residues a single device
    holding every plane would have computed — bit for bit.
    """
    _, radix, _ = partial_split(ctx.moduli)
    outs = []
    for l, p in enumerate(ctx.moduli):
        # |each term| <= 127^2, n_parts of them: far inside int32
        acc = sum(
            sym_mod_int32(t_parts[j], int(p)) * int(radix[j, l])
            for j in range(radix.shape[0])
        )
        outs.append(sym_mod_int32(acc, int(p)))
    return jnp.stack(outs, axis=0).astype(jnp.int8)
