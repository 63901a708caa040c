"""The bitcast-free exponent helpers of `core.scaling` against NumPy.

`frexp_exponent` / `exp2i` replace `jnp.frexp` / `jnp.ldexp`, whose f64
lowering bitcasts to s64 — which XLA's TPU x64 rewriter cannot compile.
They must agree with `np.frexp` / `np.ldexp` bit for bit wherever XLA can
represent the value, so the CPU pipeline stays bitwise what it was.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.scaling import exp2i, frexp_exponent, ilogb
from repro.kernels.common import split_scale_exponent


def _edges(lo: int, hi: int) -> np.ndarray:
    """Every power of two 2^e, e in [lo, hi], and its two f64 neighbours."""
    p = np.ldexp(1.0, np.arange(lo, hi + 1))
    return np.concatenate(
        [p, np.nextafter(p, 0.0), np.nextafter(p, np.inf)]
    )


def _f32_range(rng) -> np.ndarray:
    """f32 values (subnormals, extremes, powers of two, random) as f64."""
    fi = np.finfo(np.float32)
    special = np.array(
        [fi.tiny, fi.max, fi.smallest_subnormal, 1e-40, 3e-39, 1.0, 0.75],
        np.float32,
    )
    pows = np.ldexp(np.float32(1.0), np.arange(-149, 128)).astype(np.float32)
    rand = (rng.standard_normal(2000) * np.exp(rng.uniform(-80, 80, 2000)))
    return np.concatenate([special, pows, rand.astype(np.float32)]).astype(
        np.float64
    )


def _f64_range(rng) -> np.ndarray:
    """Normal f64 values over the whole exponent range, far outside f32's."""
    fi = np.finfo(np.float64)
    rand = rng.uniform(0.5, 1.0, 4000) * np.ldexp(
        1.0, rng.integers(-1021, 1024, 4000)
    )
    x = np.concatenate([[fi.tiny, fi.max], _edges(-1022, 1023), rand])
    return x[x >= fi.tiny]  # nextafter(2^-1022, 0) is subnormal


@pytest.mark.parametrize("values", ["f32_range", "f64_range"])
@pytest.mark.parametrize("sign", [1.0, -1.0])
def test_frexp_exponent_matches_numpy(rng, values, sign):
    x = sign * (_f32_range(rng) if values == "f32_range" else _f64_range(rng))
    got = np.asarray(frexp_exponent(jnp.asarray(x)))
    np.testing.assert_array_equal(got, np.frexp(x)[1])
    pos = np.abs(x)
    np.testing.assert_array_equal(
        np.asarray(ilogb(jnp.asarray(pos))), np.frexp(pos)[1] - 1
    )


def test_frexp_exponent_zero_inf_nan_and_f64_subnormals():
    """Zero, inf and nan give NumPy's 0.  f64 subnormals count as zero:
    XLA's arithmetic flushes them, and the scaling pass already treats
    rows whose max flushes to zero as zero rows."""
    x = np.array([0.0, -0.0, np.inf, -np.inf, np.nan])
    got = np.asarray(frexp_exponent(jnp.asarray(x)))
    np.testing.assert_array_equal(got, np.frexp(x)[1])
    sub = np.array([5e-324, 1e-310, np.nextafter(2.0**-1022, 0.0)])
    np.testing.assert_array_equal(
        np.asarray(frexp_exponent(jnp.asarray(sub))), np.frexp(0.0)[1]
    )


def test_exp2i_matches_numpy_ldexp_bitwise():
    """Every normal result bitwise; inf above 2^1023, and 0 below 2^-1022
    (np.ldexp's subnormals are flushed, as XLA flushes them)."""
    e = np.concatenate(
        [
            np.arange(-1200, 1200),
            [np.iinfo(np.int32).min, -(2**20), 2**20, np.iinfo(np.int32).max],
        ]
    ).astype(np.int32)
    got = np.asarray(exp2i(jnp.asarray(e)))
    with np.errstate(over="ignore"):
        want = np.ldexp(1.0, e)
    want[e < -1022] = 0.0
    np.testing.assert_array_equal(got.view(np.int64), want.view(np.int64))


def test_split_scale_exponent_is_exact():
    e = jnp.arange(-252, 253, dtype=jnp.int32)
    s1, s2 = split_scale_exponent(e)
    assert s1.dtype == s2.dtype == jnp.float32
    prod = np.asarray(s1, np.float64) * np.asarray(s2, np.float64)
    np.testing.assert_array_equal(prod, np.ldexp(1.0, np.asarray(e)))


@pytest.mark.parametrize("fn", ["frexp_exponent", "exp2i"])
def test_helpers_lower_without_64bit_bitcasts(fn):
    """The lowered programs reinterpret no 64-bit value (the TPU's x64
    rewriter has no f64 <-> s64 bitcast)."""
    if fn == "frexp_exponent":
        arg = jax.ShapeDtypeStruct((8,), jnp.float64)
        text = jax.jit(frexp_exponent).lower(arg).as_text()
    else:
        arg = jax.ShapeDtypeStruct((8,), jnp.int32)
        text = jax.jit(exp2i).lower(arg).as_text()
    assert "bitcast_convert" not in text


@pytest.mark.parametrize("routine", ["dgemm", "zgemm"])
def test_inverse_scale_tiny_operands(rng, routine):
    """Operands near 2^-500 on both sides: the inverse scaling power
    2^-(e_mu + e_nu) lies below the f64 range, C does not.  Powers of two
    pass through the pipeline exactly, so C is the unscaled product's C
    times 2^-1000, bit for bit."""
    from repro import linalg
    from repro.core.policy import GemmPolicy

    dtype = np.float64 if routine == "dgemm" else np.complex128
    a = rng.uniform(-1, 1, (16, 24)).astype(dtype)
    b = rng.uniform(-1, 1, (24, 8)).astype(dtype)
    if routine == "zgemm":
        a = a + 1j * rng.uniform(-1, 1, a.shape)
        b = b + 1j * rng.uniform(-1, 1, b.shape)
    fn = getattr(linalg, routine)
    pol = GemmPolicy(mode="fast")
    c = np.asarray(fn(a, b, policy=pol))
    tiny = np.asarray(fn(a * 2.0**-500, b * 2.0**-500, policy=pol))
    assert np.all(c != 0)
    np.testing.assert_array_equal(tiny * 2.0**1000, c)
