"""End-to-end Ozaki-II emulation accuracy + exactness of the CRT pipeline.

The key validations of the paper's claims (SIV-A):
  * the emulated product of the *quantized* matrices is EXACT (checked
    against arbitrary-precision Python integers),
  * the uniqueness condition (4) holds under both scaling modes,
  * accuracy bands: CGEMM-level at N~7, ZGEMM-level at N~13-14, and the
    complex Karatsuba formulation needs one modulus fewer than real DGEMM.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from conftest import phi_matrix
from repro.core import make_crt_context, ozaki2_cgemm, ozaki2_gemm
from repro.core import scaling
from repro.core.gemm import _n_limbs
from repro.core.residues import quantize, residues_from_quantized

M, K, N = 48, 192, 40


def _ref(a, b):
    return a.astype(np.clongdouble if np.iscomplexobj(a) else np.longdouble) @ b.astype(
        np.clongdouble if np.iscomplexobj(b) else np.longdouble
    )


def _maxrel(c, ref):
    denom = np.maximum(np.abs(ref), 1e-300)
    if np.iscomplexobj(ref):
        return float(
            max(
                np.max(np.abs(np.real(c) - np.real(ref)) / np.maximum(np.abs(np.real(ref)), 1e-300)),
                np.max(np.abs(np.imag(c) - np.imag(ref)) / np.maximum(np.abs(np.imag(ref)), 1e-300)),
            )
        )
    return float(np.max(np.abs(c - ref) / denom))


@pytest.mark.parametrize("mode", ["fast", "accu"])
@pytest.mark.parametrize("method", ["paper", "dd", "garner"])
def test_f64_accuracy(rng, mode, method):
    a = phi_matrix(rng, (M, K), 1.0, np.float64)
    b = phi_matrix(rng, (K, N), 1.0, np.float64)
    c = np.asarray(ozaki2_gemm(jnp.asarray(a), jnp.asarray(b), 16, mode, method))
    assert _maxrel(c, _ref(a, b)) < 1e-13


@pytest.mark.parametrize("mode", ["fast", "accu"])
def test_f32_accuracy(rng, mode):
    a = phi_matrix(rng, (M, K), 0.5, np.float32)
    b = phi_matrix(rng, (K, N), 0.5, np.float32)
    c = np.asarray(ozaki2_gemm(jnp.asarray(a), jnp.asarray(b), 8, mode))
    assert _maxrel(c, _ref(a, b)) < 2e-4


def test_quantized_product_is_exact(rng):
    """C' from the CRT pipeline == A'B' computed in exact Python ints."""
    ctx = make_crt_context(10)
    a = phi_matrix(rng, (8, 32), 1.0, np.float64)
    b = phi_matrix(rng, (32, 6), 1.0, np.float64)
    e_mu, e_nu = scaling.scale_fast_real(jnp.asarray(a), jnp.asarray(b), ctx)
    aq = np.asarray(quantize(jnp.asarray(a), scaling.exp2i(e_mu), 0))
    bq = np.asarray(quantize(jnp.asarray(b), scaling.exp2i(e_nu), 1))
    ai = aq.astype(object).astype(int) if False else np.vectorize(int, otypes=[object])(aq)
    bi = np.vectorize(int, otypes=[object])(bq)
    exact = ai @ bi  # arbitrary-precision integer matmul
    # uniqueness condition (4): 2 * sum |a'||b'| < P
    bound = np.vectorize(abs, otypes=[object])(ai) @ np.vectorize(abs, otypes=[object])(bi)
    assert all(2 * int(v) < ctx.P for v in bound.ravel())
    # emulated C should equal exact / (mu nu) to f64 rounding
    c = np.asarray(
        ozaki2_gemm(jnp.asarray(a), jnp.asarray(b), 10, "fast", "garner")
    )
    mu = np.ldexp(1.0, np.asarray(e_mu))
    nu = np.ldexp(1.0, np.asarray(e_nu))
    expect = np.array(
        [[float(exact[i, j]) / (mu[i] * nu[j]) for j in range(6)] for i in range(8)]
    )
    np.testing.assert_allclose(c, expect, rtol=1e-15, atol=0)


def test_condition4_accurate_mode_extreme_range(rng):
    """Accurate mode must maintain (4) even at wide dynamic range (phi=4)."""
    ctx = make_crt_context(14)
    a = phi_matrix(rng, (M, K), 4.0, np.float64)
    b = phi_matrix(rng, (K, N), 4.0, np.float64)
    e_mu, e_nu = scaling.scale_accurate_real(jnp.asarray(a), jnp.asarray(b), ctx)
    aq = np.asarray(quantize(jnp.asarray(a), scaling.exp2i(e_mu), 0))
    bq = np.asarray(quantize(jnp.asarray(b), scaling.exp2i(e_nu), 1))
    ai = np.vectorize(int, otypes=[object])(np.abs(aq))
    bi = np.vectorize(int, otypes=[object])(np.abs(bq))
    bound = ai @ bi
    assert all(2 * int(v) < ctx.P for v in bound.ravel())


def _medrel(c, ref):
    r = np.maximum(
        np.abs(np.real(c) - np.real(ref))
        / np.maximum(np.abs(np.real(ref)), 1e-300),
        np.abs(np.imag(c) - np.imag(ref))
        / np.maximum(np.abs(np.imag(ref)), 1e-300),
    )
    return float(np.median(r))


@pytest.mark.parametrize("phi", [0.5, 1.0, 2.0])
def test_zgemm_band(rng, phi):
    """Paper Fig. 5: ZGEMM-level accuracy from N=13-14 (complex).

    Uses the median relative error: the max-rel metric is dominated by
    near-cancelling output entries at these small test sizes."""
    a = phi_matrix(rng, (M, K), phi, np.complex128)
    b = phi_matrix(rng, (K, N), phi, np.complex128)
    ref = _ref(a, b)
    native_max = _maxrel(np.asarray(a @ b), ref)
    emul_med = _medrel(
        np.asarray(ozaki2_cgemm(jnp.asarray(a), jnp.asarray(b), 14, "accu")), ref
    )
    assert emul_med < max(native_max, 1e-13)


def test_karatsuba_no_accuracy_penalty(rng):
    """Residue-ring Karatsuba is exact modular arithmetic, so the complex
    emulation at N moduli stays within the real-DGEMM band at the same N
    (this is why ZGEMM needs 13 moduli where real DGEMM needs 14)."""
    for n_mod in (13, 14):
        a = phi_matrix(rng, (M, K), 1.0, np.complex128)
        b = phi_matrix(rng, (K, N), 1.0, np.complex128)
        ar = phi_matrix(rng, (M, K), 1.0, np.float64)
        br = phi_matrix(rng, (K, N), 1.0, np.float64)
        err_c = _maxrel(
            np.asarray(ozaki2_cgemm(jnp.asarray(a), jnp.asarray(b), n_mod, "fast")),
            _ref(a, b),
        )
        err_r = _maxrel(
            np.asarray(ozaki2_gemm(jnp.asarray(ar), jnp.asarray(br), n_mod, "fast")),
            _ref(ar, br),
        )
        assert err_c < err_r * 50  # same band (modulo instance noise)


def test_complex_formulations_agree_exactly(rng):
    """(7), (8) and Karatsuba compute identical residues => identical C."""
    a = phi_matrix(rng, (M, K), 1.0, np.complex64)
    b = phi_matrix(rng, (K, N), 1.0, np.complex64)
    outs = [
        np.asarray(ozaki2_cgemm(jnp.asarray(a), jnp.asarray(b), 7, "fast", formulation=f))
        for f in ("karatsuba", "block_a", "block_b")
    ]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


def test_n_blocking_is_exact(rng):
    a = phi_matrix(rng, (M, K), 1.0, np.complex64)
    b = phi_matrix(rng, (K, N), 1.0, np.complex64)
    full = np.asarray(ozaki2_cgemm(jnp.asarray(a), jnp.asarray(b), 7))
    blocked = np.asarray(ozaki2_cgemm(jnp.asarray(a), jnp.asarray(b), 7, n_block=16))
    np.testing.assert_array_equal(full, blocked)


def test_batched_gemm(rng):
    a = phi_matrix(rng, (3, 16, 32), 0.5, np.float32)
    b = phi_matrix(rng, (3, 32, 8), 0.5, np.float32)
    c = np.asarray(ozaki2_gemm(jnp.asarray(a), jnp.asarray(b), 8))
    ref = np.einsum("bij,bjk->bik", a.astype(np.float64), b.astype(np.float64))
    np.testing.assert_allclose(c, ref, rtol=2e-4, atol=1e-6)


def test_bitwise_reproducible(rng):
    a = phi_matrix(rng, (M, K), 1.0, np.float64)
    b = phi_matrix(rng, (K, N), 1.0, np.float64)
    c1 = np.asarray(ozaki2_gemm(jnp.asarray(a), jnp.asarray(b), 13))
    c2 = np.asarray(ozaki2_gemm(jnp.asarray(a), jnp.asarray(b), 13))
    np.testing.assert_array_equal(c1, c2)


def test_ozaki1_baseline(rng):
    """The paper's comparison baseline (SIV 'OS I-S'), reimplemented: S=9
    slices reach DGEMM-level accuracy at S(S+1)/2 = 45 int8 GEMMs where
    Ozaki-II needs 14-16 — the quadratic-vs-linear gap behind Figs. 10/12."""
    from repro.core.ozaki1 import int8_gemm_count, ozaki1_cgemm, ozaki1_gemm

    a = phi_matrix(rng, (M, K), 1.0, np.float64)
    b = phi_matrix(rng, (K, N), 1.0, np.float64)
    err9 = _maxrel(np.asarray(ozaki1_gemm(jnp.asarray(a), jnp.asarray(b), 9)), _ref(a, b))
    err5 = _maxrel(np.asarray(ozaki1_gemm(jnp.asarray(a), jnp.asarray(b), 5)), _ref(a, b))
    assert err9 < 1e-11 and err5 > err9 * 100  # accuracy scales with slices
    assert int8_gemm_count(9) == 45
    az = phi_matrix(rng, (M, K), 1.0, np.complex128)
    bz = phi_matrix(rng, (K, N), 1.0, np.complex128)
    errz = _maxrel(np.asarray(ozaki1_cgemm(jnp.asarray(az), jnp.asarray(bz), 9)), _ref(az, bz))
    assert errz < 1e-11


def test_prepared_operand_matches_direct(rng):
    """Beyond-paper: pre-residue-cast A amortizes step 1 across calls and
    is bit-compatible with the direct fast-mode pipeline."""
    from repro.core import PreparedOperand, gemm_prepared

    a = phi_matrix(rng, (M, K), 1.0, np.float64)
    prep = PreparedOperand(jnp.asarray(a), 14)
    for seed in range(3):
        b = phi_matrix(np.random.default_rng(seed), (K, N), 1.0, np.float64)
        c1 = np.asarray(gemm_prepared(prep, jnp.asarray(b)))
        c2 = np.asarray(ozaki2_gemm(jnp.asarray(a), jnp.asarray(b), 14, "fast"))
        np.testing.assert_array_equal(c1, c2)


def test_zero_and_degenerate_inputs():
    a = jnp.zeros((4, 8), jnp.float64)
    b = jnp.ones((8, 3), jnp.float64)
    c = np.asarray(ozaki2_gemm(a, b, 8))
    np.testing.assert_array_equal(c, 0.0)
    # single row/col degenerate values
    a2 = jnp.asarray(np.array([[1e300, 1e-300]] * 2))
    b2 = jnp.asarray(np.array([[1.0], [1.0]]))
    c2 = np.asarray(ozaki2_gemm(a2, b2, 12))
    assert np.isfinite(c2).all()
