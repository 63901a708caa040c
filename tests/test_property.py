"""Hypothesis property tests on the system's numeric invariants."""
import numpy as np
import jax.numpy as jnp
import pytest

pytest.importorskip(
    "hypothesis", reason="optional dependency: property tests need hypothesis"
)
from hypothesis import given, settings, strategies as st

from repro.core.moduli import make_crt_context
from repro.core.residues import (
    residues_from_quantized,
    split_limbs,
    sym_mod_int32,
    sym_mod_small,
)
from repro.core import crt

SET = settings(max_examples=25, deadline=None)


@given(
    st.integers(min_value=-(2**60), max_value=2**60),
    st.integers(min_value=0, max_value=19),
)
@SET
def test_residue_of_any_integer_is_exact(x, mod_idx):
    """Residue extraction via limb split == exact Python mod, for any
    f64-representable integer."""
    ctx = make_crt_context(20)
    p = ctx.moduli[mod_idx]
    xf = float(x)
    if int(xf) != x:  # keep only exactly-representable ints
        x = int(xf)
    arr = jnp.asarray([[xf]], jnp.float64)
    res = residues_from_quantized(arr, ctx, n_limbs=3)
    r = int(res[mod_idx, 0, 0])
    assert (r - x) % p == 0
    assert abs(r) <= (p - 1) // 2


@given(st.integers(min_value=-(2**62), max_value=2**62), st.integers(2, 5))
@SET
def test_split_limbs_reconstructs(x, n_limbs):
    xf = float(x)
    x = int(xf)
    if abs(x) >= 2 ** (24 * n_limbs):
        return
    limbs = np.asarray(split_limbs(jnp.asarray([xf], jnp.float64), n_limbs))
    val = sum(int(limbs[i, 0]) * (1 << (24 * i)) for i in range(n_limbs))
    assert val == x


@given(
    st.integers(min_value=-(2**31) + 1, max_value=2**31 - 1),
    st.sampled_from([3, 127, 199, 251, 255]),
)
@SET
def test_sym_mod_int32(v, p):
    r = int(sym_mod_int32(jnp.asarray([v], jnp.int32), p)[0])
    assert (r - v) % p == 0
    assert abs(r) <= (p - 1) // 2


@given(
    st.integers(min_value=-(2**17), max_value=2**17),
    st.sampled_from([3, 127, 199, 251, 255]),
)
@SET
def test_sym_mod_small_f32(v, p):
    r = int(np.asarray(sym_mod_small(jnp.asarray([float(v)], jnp.float32), float(p), float((p - 1) // 2)))[0])
    assert (r - v) % p == 0
    assert abs(r) <= (p - 1) // 2


@given(st.data())
@settings(max_examples=15, deadline=None)
def test_crt_roundtrip_random_integers(data):
    """Any integer |x| < P/2: residues -> (garner|paper|dd) -> x exactly."""
    n = data.draw(st.integers(min_value=2, max_value=16))
    ctx = make_crt_context(n)
    # condition (4) keeps |C'| strictly below P/2 with >= 2 bits of scaling
    # slack; draw within 49% of P (the boundary itself is unreachable)
    half = int(ctx.P * 0.49)
    x = data.draw(st.integers(min_value=-half, max_value=half))
    e = np.zeros((n, 1, 1), np.int8)
    for l, p in enumerate(ctx.moduli):
        r = x % p
        if r > (p - 1) // 2:
            r -= p
        e[l, 0, 0] = r
    # absolute error floors (in C' units): garner converts digits MS-first
    # (~P*2^-100); dd accumulates N products of ~P*127 (~P*2^-93); the paper
    # eq.(5) split keeps ~P*2^-80 (w_lo parts are rounded doubles).  All are
    # far below the scheme's truncation floor (DESIGN.md S2).
    tols = {"garner": 2.0**-100, "dd": 2.0**-93, "paper": 2.0**-78}
    for method in ("garner", "dd", "paper"):
        hi, lo = crt.reconstruct(jnp.asarray(e), ctx, method)
        got = float(hi[0, 0]) + float(lo[0, 0])
        tol = max(abs(x) * 2.0**-90, float(ctx.P) * tols[method], 1e-9)
        assert abs(got - float(x)) <= tol, (method, n, x, got)


@given(
    st.floats(0.0, 3.0),
    st.integers(min_value=0, max_value=2**31 - 1),
    st.sampled_from([8, 12, 16]),
)
@settings(max_examples=10, deadline=None)
def test_condition4_fast_mode(phi, seed, n_mod):
    """The uniqueness condition (4): 2 sum_h |a'||b'| < P must hold for the
    fast-mode scaling across random dynamic ranges (else CRT is ambiguous
    and the whole scheme silently corrupts)."""
    import jax.numpy as jnp

    from repro.core import scaling
    from repro.core.residues import quantize

    ctx = make_crt_context(n_mod)
    rng = np.random.default_rng(seed)
    a = (rng.random((8, 48)) - 0.5) * np.exp(rng.standard_normal((8, 48)) * phi)
    b = (rng.random((48, 6)) - 0.5) * np.exp(rng.standard_normal((48, 6)) * phi)
    e_mu, e_nu = scaling.scale_fast_real(jnp.asarray(a), jnp.asarray(b), ctx)
    aq = np.asarray(quantize(jnp.asarray(a), scaling.exp2i(e_mu), 0))
    bq = np.asarray(quantize(jnp.asarray(b), scaling.exp2i(e_nu), 1))
    ai = np.vectorize(int, otypes=[object])(np.abs(aq))
    bi = np.vectorize(int, otypes=[object])(np.abs(bq))
    bound = ai @ bi
    assert all(2 * int(v) < ctx.P for v in bound.ravel())


@given(
    st.floats(-1e6, 1e6, allow_subnormal=False),
    st.floats(-1e6, 1e6, allow_subnormal=False),
)
@SET
def test_two_sum_exact(a, b):
    from repro.core.expansion import two_sum

    s, e = two_sum(jnp.float64(a), jnp.float64(b))
    # two_sum is exact: s + e == a + b with s = fl(a+b)
    import math

    from fractions import Fraction

    assert Fraction(float(s)) + Fraction(float(e)) == Fraction(a) + Fraction(b)
    assert float(s) == a + b


@given(
    st.floats(-1e15, 1e15, allow_subnormal=False),
    st.floats(-1e15, 1e15, allow_subnormal=False),
)
@SET
def test_two_prod_exact(a, b):
    from fractions import Fraction

    from hypothesis import assume

    from repro.core.expansion import two_prod

    # two_prod's error-free guarantee requires no under/overflow of a*b
    assume(a == 0 or b == 0 or 1e-280 < abs(a * b) < 1e280)
    p, e = two_prod(jnp.float64(a), jnp.float64(b))
    assert Fraction(float(p)) + Fraction(float(e)) == Fraction(a) * Fraction(b)


# ------------------------------- perfmodel block selection (repro.tune base)


def _round_up(x, m):
    return -(-x // m) * m


@given(
    st.integers(min_value=1, max_value=5000),
    st.sampled_from([8, 32, 64, 128, 192, 256, 384, 512, 1024]),
    st.sampled_from([None, 8, 32, 128]),
)
@SET
def test_select_block_divides_padding(dim, block, align):
    """The selected block always divides the padded dim, and shrinking never
    pads MORE than the static default block would — the two invariants the
    pad-and-slice kernels (and so the autotuner's safety argument) rest on."""
    from repro.core import perfmodel

    b = perfmodel.select_block(dim, block, align)
    pad = perfmodel.padded_dim(dim, block, align)
    assert b >= 1
    assert pad % b == 0, f"block {b} does not divide padded dim {pad}"
    assert pad >= dim
    assert pad <= _round_up(dim, block), (
        f"shrunk block {b} pads {dim}->{pad}, worse than the static "
        f"block {block}'s {_round_up(dim, block)}"
    )
    # an aligned request stays aligned unless the dim itself is smaller
    if align is not None and block % align == 0 and dim > block:
        assert b % align == 0 or b == dim


@given(
    st.integers(min_value=1, max_value=5000),
    st.sampled_from([8, 32, 64, 128, 192, 256, 384, 512, 1024]),
    st.sampled_from([None, 8, 32, 128]),
)
@SET
def test_select_block_small_dim_is_exact(dim, block, align):
    """A dim no larger than the block never pads at all (block == dim)."""
    from repro.core import perfmodel

    if dim <= block:
        assert perfmodel.select_block(dim, block, align) == dim
        assert perfmodel.padded_dim(dim, block, align) == dim


def test_select_block_rejects_degenerate():
    from repro.core import perfmodel

    with pytest.raises(ValueError):
        perfmodel.select_block(0, 256, 128)
    with pytest.raises(ValueError):
        perfmodel.select_block(-3, 256, 128)
    with pytest.raises(ValueError):
        perfmodel.select_block(64, 0, 128)
