"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracles.

The three integer kernels must be BIT-EXACT against the oracles; the Garner
reconstruction kernel is compared at its double-single precision.  The
modulus-batched kernels (one `pallas_call` for all N planes) must be
BIT-IDENTICAL to the retained per-modulus launches, including ragged
(non-block-divisible) shapes and chunked-K carries, and the pipeline's
launch counts must match the perfmodel's `kernel_launch_count` (certified
through the shared `repro.analysis.LaunchCountPass`).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from conftest import FAST_K, FAST_M, FAST_N, phi_matrix
from repro.analysis import certify_launch_count
from repro.core import perfmodel
from repro.core.executor import execute_plan
from repro.core.moduli import make_crt_context
from repro.core.plan import make_plan
from repro.kernels import (
    FusedBackend,
    KernelBackend,
    PerModulusKernelBackend,
    crt_garner,
    int8_mod_gemm,
    int8_mod_gemm_batched,
    karatsuba_mod_gemm,
    karatsuba_mod_gemm_batched,
    ozaki2_cgemm_kernels,
    ozaki2_gemm_kernels,
    residue_cast,
)
from repro.kernels import ref
from repro.kernels.common import split_scale_exponent

SHAPES_MK = [(128, 256), (256, 512), (8, 128)]
MODULI_SWEEP = [3, 199, 251, 255]


@pytest.mark.parametrize("m,k", SHAPES_MK)
@pytest.mark.parametrize("n_mod", [2, 7, 13])
@pytest.mark.parametrize("scale_axis", [0, 1])
def test_residue_cast_sweep(rng, m, k, n_mod, scale_axis):
    ctx = make_crt_context(n_mod)
    a = (rng.standard_normal((m, k)) * 10.0 ** rng.integers(-3, 4)).astype(np.float32)
    dim = m if scale_axis == 0 else k
    e = rng.integers(-10, 20, size=dim).astype(np.int32)
    s1, s2 = split_scale_exponent(jnp.asarray(e))
    kw = dict(moduli=ctx.moduli, n_limbs=2, scale_axis=scale_axis)
    out = residue_cast(jnp.asarray(a), s1, s2, bm=min(128, m), bk=128, **kw)
    expect = ref.residue_cast_ref(jnp.asarray(a), s1, s2, **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("m,n,k", [(128, 128, 256), (256, 64, 512), (8, 128, 128)])
@pytest.mark.parametrize("p", MODULI_SWEEP)
def test_int8_mod_gemm_sweep(rng, m, n, k, p):
    h = (p - 1) // 2
    a = rng.integers(-h, h + 1, size=(m, k)).astype(np.int8)
    b = rng.integers(-h, h + 1, size=(k, n)).astype(np.int8)
    out = int8_mod_gemm(jnp.asarray(a), jnp.asarray(b), p=p, bm=128, bn=64, bk=128)
    expect = ref.int8_mod_gemm_ref(jnp.asarray(a), jnp.asarray(b), p=p)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("p", MODULI_SWEEP)
def test_karatsuba_fused_sweep(rng, p):
    m, n, k = 128, 128, 256
    h = (p - 1) // 2
    mats = [
        rng.integers(-h, h + 1, size=s).astype(np.int8)
        for s in [(m, k), (m, k), (k, n), (k, n)]
    ]
    cr, ci = karatsuba_mod_gemm(*map(jnp.asarray, mats), p=p, bm=128, bn=128, bk=128)
    er, ei = ref.karatsuba_mod_gemm_ref(*map(jnp.asarray, mats), p=p)
    np.testing.assert_array_equal(np.asarray(cr), np.asarray(er))
    np.testing.assert_array_equal(np.asarray(ci), np.asarray(ei))


@pytest.mark.parametrize("n_mod", [2, 7, 13, 16])
@pytest.mark.parametrize("out_dd", [False, True])
def test_crt_garner_sweep(rng, n_mod, out_dd):
    ctx = make_crt_context(n_mod)
    m, n = 128, 128
    e = np.stack(
        [
            rng.integers(-(p - 1) // 2, (p - 1) // 2 + 1, size=(m, n))
            for p in ctx.moduli
        ]
    ).astype(np.int8)
    emu = rng.integers(10, 60, size=m).astype(np.int32)
    enu = rng.integers(10, 60, size=n).astype(np.int32)
    out = crt_garner(jnp.asarray(e), jnp.asarray(emu), jnp.asarray(enu), ctx, out_dd=out_dd)
    expect = np.asarray(ref.crt_garner_ref(jnp.asarray(e), jnp.asarray(emu), jnp.asarray(enu), ctx))
    got = (
        np.asarray(out[0], np.float64) + np.asarray(out[1], np.float64)
        if out_dd
        else np.asarray(out, np.float64)
    )
    tol = 2.0**-44 if out_dd else 2.0**-21
    denom = np.maximum(np.abs(expect), np.max(np.abs(expect)) * 1e-6 + 1e-300)
    assert np.max(np.abs(got - expect) / denom) < tol


def test_full_kernel_gemm_pipeline(rng):
    m, k, n = 256, 512, 256
    a = (rng.random((m, k)) - 0.5).astype(np.float32)
    b = (rng.random((k, n)) - 0.5).astype(np.float32)
    y = np.asarray(ozaki2_gemm_kernels(jnp.asarray(a), jnp.asarray(b), n_moduli=8))
    expect = a.astype(np.float64) @ b.astype(np.float64)
    scale = np.max(np.abs(expect))
    assert np.max(np.abs(y - expect)) / scale < 1e-5


def test_full_kernel_cgemm_pipeline(rng):
    m, k, n = 256, 512, 256
    a = ((rng.random((m, k)) - 0.5) + 1j * (rng.random((m, k)) - 0.5)).astype(np.complex64)
    b = ((rng.random((k, n)) - 0.5) + 1j * (rng.random((k, n)) - 0.5)).astype(np.complex64)
    y = np.asarray(ozaki2_cgemm_kernels(jnp.asarray(a), jnp.asarray(b), n_moduli=7))
    expect = a.astype(np.complex128) @ b.astype(np.complex128)
    scale = np.max(np.abs(expect))
    assert np.max(np.abs(y - expect)) / scale < 1e-5


@pytest.mark.parametrize("formulation", ["block_a", "block_b"])
def test_kernel_block_formulations_match_karatsuba(rng, formulation):
    """The block embeddings (eqs. 7/8), composed in the shared executor over
    `int8_mod_gemm`, produce residues identical to the fused-Karatsuba
    kernel => bitwise-equal outputs on the kernel path too."""
    m, k, n = 128, 128, 128
    a = ((rng.random((m, k)) - 0.5) + 1j * (rng.random((m, k)) - 0.5)).astype(np.complex64)
    b = ((rng.random((k, n)) - 0.5) + 1j * (rng.random((k, n)) - 0.5)).astype(np.complex64)
    base = np.asarray(ozaki2_cgemm_kernels(jnp.asarray(a), jnp.asarray(b), n_moduli=4))
    alt = np.asarray(
        ozaki2_cgemm_kernels(
            jnp.asarray(a), jnp.asarray(b), n_moduli=4, formulation=formulation
        )
    )
    np.testing.assert_array_equal(base, alt)


@pytest.mark.parametrize(
    "b,s,h,kv,d", [(2, 256, 4, 2, 64), (1, 512, 8, 1, 32), (2, 128, 4, 4, 64)]
)
@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
def test_flash_attention_sweep(rng, b, s, h, kv, d, dtype):
    from repro.kernels import flash_attention

    dt = jnp.dtype(dtype)
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), dt)
    k = jnp.asarray(rng.standard_normal((b, s, kv, d)), dt)
    v = jnp.asarray(rng.standard_normal((b, s, kv, d)), dt)
    out = flash_attention(q, k, v, causal=True, bq=128, bk=128)
    expect = ref.flash_attention_ref(q, k, v, causal=True)
    tol = 2e-5 if dt == jnp.float32 else 2e-2
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                 expect.astype(jnp.float32)))) < tol


def test_kernel_pipeline_matches_core_residues(rng):
    """Kernel path and core path produce identical int8 residue planes."""
    from repro.core import scaling
    from repro.core.residues import quantize, residues_from_quantized

    ctx = make_crt_context(7)
    m, k = 128, 256
    a = (rng.random((m, k)) - 0.5).astype(np.float32)
    e = rng.integers(0, 20, size=m).astype(np.int32)
    s1, s2 = split_scale_exponent(jnp.asarray(e))
    kern = residue_cast(jnp.asarray(a), s1, s2, moduli=ctx.moduli, n_limbs=2)
    aq = quantize(jnp.asarray(a, jnp.float64), scaling.exp2i(jnp.asarray(e)), 0)
    core = residues_from_quantized(aq, ctx, 2)
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(core))


# ================================================= modulus-batched kernels


BATCHED = KernelBackend(interpret=True)
PER_MODULUS = PerModulusKernelBackend(interpret=True)


def _garner_plan(dtype, mode="fast", formulation=None, n_moduli=5, n_block=None):
    return make_plan(
        dtype, n_moduli=n_moduli, mode=mode, method="garner",
        formulation=formulation, n_block=n_block,
    )


def _operands(rng, dtype, m=FAST_M, k=FAST_K, n=FAST_N):
    a = jnp.asarray(phi_matrix(rng, (m, k), 0.5, dtype))
    b = jnp.asarray(phi_matrix(rng, (k, n), 0.5, dtype))
    return a, b


@pytest.mark.parametrize("mode", ["fast", "accu"])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_batched_matches_per_modulus_real(rng, dtype, mode):
    """Tentpole parity: the single-launch batched kernels are bitwise
    identical to the retained per-modulus launches (real pipelines)."""
    a, b = _operands(rng, dtype)
    plan = _garner_plan(dtype, mode)
    got = np.asarray(execute_plan(plan, a, b, BATCHED))
    want = np.asarray(execute_plan(plan, a, b, PER_MODULUS))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("formulation", ["karatsuba", "block_a", "block_b"])
@pytest.mark.parametrize("mode", ["fast", "accu"])
@pytest.mark.parametrize("dtype", [np.complex64, np.complex128])
def test_batched_matches_per_modulus_complex(rng, dtype, mode, formulation):
    """Tentpole parity, complex: batched vs per-modulus across all three
    Fig. 1 formulations (Karatsuba uses the fused kernel on both sides;
    the block embeddings compose over the real residue product)."""
    a, b = _operands(rng, dtype)
    plan = _garner_plan(dtype, mode, formulation)
    got = np.asarray(execute_plan(plan, a, b, BATCHED))
    want = np.asarray(execute_plan(plan, a, b, PER_MODULUS))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("p", [3, 251])
def test_int8_mod_gemm_ragged_regression(rng, p):
    """Non-block-divisible shapes previously raised ValueError on the kernel
    path; pad-and-slice must keep them bit-exact (m,n,k prime)."""
    m, n, k = 37, 29, 53
    h = (p - 1) // 2
    a = rng.integers(-h, h + 1, size=(m, k)).astype(np.int8)
    b = rng.integers(-h, h + 1, size=(k, n)).astype(np.int8)
    out = int8_mod_gemm(jnp.asarray(a), jnp.asarray(b), p=p, bm=16, bn=16, bk=16)
    expect = ref.int8_mod_gemm_ref(jnp.asarray(a), jnp.asarray(b), p=p)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_karatsuba_ragged_regression(rng):
    m, n, k, p = 37, 29, 53, 251
    h = (p - 1) // 2
    mats = [
        rng.integers(-h, h + 1, size=s).astype(np.int8)
        for s in [(m, k), (m, k), (k, n), (k, n)]
    ]
    cr, ci = karatsuba_mod_gemm(*map(jnp.asarray, mats), p=p, bm=16, bn=16, bk=16)
    er, ei = ref.karatsuba_mod_gemm_ref(*map(jnp.asarray, mats), p=p)
    np.testing.assert_array_equal(np.asarray(cr), np.asarray(er))
    np.testing.assert_array_equal(np.asarray(ci), np.asarray(ei))


def test_karatsuba_default_tile_matches_the_static_blocks(rng):
    """The shape-and-VMEM default tile of the complex residue product
    against the explicit static (256, 256, 512) blocks, at a shape with a
    ragged K, where the two run different tiles ((384, 384, 384) against
    (256, 256, 384)) and each spans several on every axis: the residues
    are bitwise equal."""
    from repro.kernels.common import (
        DEFAULT_GEMM_BLOCKS,
        block_and_padded,
        resolve_blocks,
    )

    m = n = 1536
    k = 1100
    ctx = make_crt_context(3)
    runs = [
        [block_and_padded(d, b, align=128)[0] for d, b in zip((m, n, k), blocks)]
        for blocks in (resolve_blocks("kernel", "complex", m, n, k), DEFAULT_GEMM_BLOCKS)
    ]
    assert runs == [[384, 384, 384], [256, 256, 384]]
    mats = [
        jnp.asarray(np.stack([
            rng.integers(-(p // 2), p // 2 + 1, size=s).astype(np.int8)
            for p in ctx.moduli
        ]))
        for s in [(m, k), (m, k), (k, n), (k, n)]
    ]
    got = karatsuba_mod_gemm_batched(*mats, moduli=ctx.moduli)
    bm, bn, bk = DEFAULT_GEMM_BLOCKS
    want = karatsuba_mod_gemm_batched(*mats, moduli=ctx.moduli,
                                      bm=bm, bn=bn, bk=bk)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_full_pipeline_ragged_default_blocks(rng):
    """m=257 exceeds the default 256-row block and is not divisible by it —
    exactly the case that raised before pad-and-slice; the padded pipeline
    must stay inside the f32 accuracy band and match per-modulus bitwise."""
    m, k, n = 257, 131, 67
    a = (rng.random((m, k)) - 0.5).astype(np.float32)
    b = (rng.random((k, n)) - 0.5).astype(np.float32)
    y = np.asarray(ozaki2_gemm_kernels(jnp.asarray(a), jnp.asarray(b), n_moduli=8))
    expect = a.astype(np.float64) @ b.astype(np.float64)
    assert np.max(np.abs(y - expect)) / np.max(np.abs(expect)) < 1e-5
    plan = _garner_plan(np.float32, n_moduli=8)
    want = np.asarray(
        execute_plan(plan, jnp.asarray(a), jnp.asarray(b), PER_MODULUS)
    )
    np.testing.assert_array_equal(y, want)


@pytest.mark.parametrize("dtype", [np.float32, np.complex64])
def test_ragged_n_block_split(rng, dtype):
    """n_block=3 on n=FAST_N leaves a ragged tail block; the kernel path
    must produce the same bits as the unblocked run (same residues sliced)."""
    a, b = _operands(rng, dtype)
    formulation = "karatsuba" if np.issubdtype(dtype, np.complexfloating) else None
    full = np.asarray(
        execute_plan(_garner_plan(dtype, formulation=formulation), a, b, BATCHED)
    )
    blocked = np.asarray(
        execute_plan(
            _garner_plan(dtype, formulation=formulation, n_block=3), a, b, BATCHED
        )
    )
    np.testing.assert_array_equal(full, blocked)


def test_chunked_k_carry_epilogue(rng, monkeypatch):
    """Acceptance: chunked-K stays on the batched path — one launch per
    K-chunk, inter-chunk sym_mod folded into the kernel carry epilogue, and
    the result is bitwise identical to the single-chunk run.  Both the real
    product and the Karatsuba (R, I) pairs chunk through the one shared
    `chunked_residue_matmul` loop, so a single K_CHUNK_LIMIT patch governs
    both; the un-chunked baselines are computed BEFORE patching."""
    import repro.core.executor as executor

    a, b = _operands(rng, np.float32, k=160)
    plan = _garner_plan(np.float32)
    ca, cb = _operands(rng, np.complex64, k=160)
    cplan = _garner_plan(np.complex64, formulation="karatsuba")
    whole = np.asarray(execute_plan(plan, a, b, BATCHED))
    cwhole = np.asarray(execute_plan(cplan, ca, cb, BATCHED))

    monkeypatch.setattr(executor, "K_CHUNK_LIMIT", 64)
    chunked = np.asarray(execute_plan(plan, a, b, BATCHED))
    np.testing.assert_array_equal(whole, chunked)
    # 3 chunks of k=160 -> 2 casts + 3 products + 1 reconstruct = 6 launches
    want = perfmodel.kernel_launch_count(5, "real", n_chunks=3)
    assert want == 6
    assert certify_launch_count(
        want, lambda x, y: execute_plan(plan, x, y, BATCHED), a, b
    ) == []

    # complex Karatsuba: CR/CI chunk carries thread through the fused kernel
    cchunked = np.asarray(execute_plan(cplan, ca, cb, BATCHED))
    np.testing.assert_array_equal(cwhole, cchunked)


@pytest.mark.parametrize("n_moduli", [3, 7])
def test_launch_counts_independent_of_n(rng, n_moduli):
    """Acceptance: exactly one `pallas_call` per cast, one for the modular
    product, one for reconstruction — at ANY modulus count — while the
    per-modulus reference scales with N.  Counts must agree with the
    perfmodel's `kernel_launch_count` (which drives formulation='auto')."""
    a, b = _operands(rng, np.float32)
    plan = _garner_plan(np.float32, n_moduli=n_moduli)
    want = perfmodel.kernel_launch_count(n_moduli, "real")
    assert want == 4
    assert certify_launch_count(
        want, lambda x, y: execute_plan(plan, x, y, BATCHED), a, b
    ) == []
    want_pm = perfmodel.kernel_launch_count(
        n_moduli, "real", modulus_batched=False
    )
    assert want_pm == 3 + n_moduli
    assert certify_launch_count(
        want_pm, lambda x, y: execute_plan(plan, x, y, PER_MODULUS), a, b
    ) == []


@pytest.mark.parametrize("formulation", ["karatsuba", "block_a"])
def test_launch_counts_complex(rng, formulation):
    ca, cb = _operands(rng, np.complex64)
    plan = _garner_plan(np.complex64, formulation=formulation, n_moduli=4)
    # stacked casts (re+im together), one batched product, stacked CR/CI
    # reconstruction: 4 launches total regardless of N or formulation
    want = perfmodel.kernel_launch_count(4, formulation)
    assert want == 4
    assert certify_launch_count(
        want, lambda x, y: execute_plan(plan, x, y, BATCHED), ca, cb
    ) == []
    want_pm = perfmodel.kernel_launch_count(
        4, formulation, modulus_batched=False
    )
    assert certify_launch_count(
        want_pm, lambda x, y: execute_plan(plan, x, y, PER_MODULUS), ca, cb
    ) == []


def test_batched_kernels_direct_parity(rng):
    """Kernel-level parity: one batched call == N per-modulus calls, with
    and without a carry operand."""
    ctx = make_crt_context(4)
    m, n, k = 32, 24, 48
    ares = rng.integers(-127, 128, size=(4, m, k)).astype(np.int8)
    bres = rng.integers(-127, 128, size=(4, k, n)).astype(np.int8)
    carry = rng.integers(-100, 101, size=(4, m, n)).astype(np.int8)
    got = np.asarray(
        int8_mod_gemm_batched(
            jnp.asarray(ares), jnp.asarray(bres), moduli=ctx.moduli,
            carry=jnp.asarray(carry),
        )
    )
    for l, p in enumerate(ctx.moduli):
        exact = ares[l].astype(np.int64) @ bres[l].astype(np.int64) + carry[l]
        r = exact % p
        r = np.where(r > (p - 1) // 2, r - p, r)
        np.testing.assert_array_equal(got[l], r)
    mats = [
        rng.integers(-127, 128, size=s).astype(np.int8)
        for s in [(4, m, k), (4, m, k), (4, k, n), (4, k, n)]
    ]
    crb, cib = karatsuba_mod_gemm_batched(
        *map(jnp.asarray, mats), moduli=ctx.moduli
    )
    for l, p in enumerate(ctx.moduli):
        er, ei = ref.karatsuba_mod_gemm_ref(
            *(jnp.asarray(mm[l]) for mm in mats), p=int(p)
        )
        np.testing.assert_array_equal(np.asarray(crb)[l], np.asarray(er))
        np.testing.assert_array_equal(np.asarray(cib)[l], np.asarray(ei))


# ---------------------------------------------- block shrink (pad economics)


@pytest.mark.parametrize("m", [129, 257])
def test_block_shrink_just_over_multiple(rng, m):
    """ROADMAP follow-up from PR 2: a dim just above a block multiple picks
    the next-smaller legal block instead of padding ~2x, behind the
    perfmodel-visible BLOCK_SHRINK knob — and the padded pipeline stays
    bitwise identical (zero padding is residue-exact either way)."""
    from repro.kernels.common import block_and_padded

    # m=129 < 256 shrinks the block to the dim (no padding at all);
    # m=257 > 256 picks the aligned 128 block and pads to 384, not 512
    expect = {129: (129, 129), 257: (128, 384)}[m]
    assert block_and_padded(m, 256, align=128) == expect
    assert perfmodel.select_block(m, 256, 128) == expect[0]
    assert perfmodel.padded_dim(m, 256, 128) == expect[1]
    assert perfmodel.padded_dim(m, 256, 128) < 2 * m  # never ~2x anymore

    # the knob restores the legacy round-up (the economics are visible)
    perfmodel.BLOCK_SHRINK = False
    try:
        legacy = block_and_padded(m, 256, align=128)
        assert legacy == ((129, 129) if m == 129 else (256, 512))
    finally:
        perfmodel.BLOCK_SHRINK = True

    # numerics: shrunken blocks are still the same bits as the reference
    k, n = 40, 33
    a = (rng.random((m, k)) - 0.5).astype(np.float32)
    b = (rng.random((k, n)) - 0.5).astype(np.float32)
    plan = _garner_plan(np.float32, n_moduli=6)
    got = np.asarray(execute_plan(plan, jnp.asarray(a), jnp.asarray(b), BATCHED))
    want = np.asarray(
        execute_plan(plan, jnp.asarray(a), jnp.asarray(b), PER_MODULUS)
    )
    np.testing.assert_array_equal(got, want)
    expect_f64 = a.astype(np.float64) @ b.astype(np.float64)
    assert np.max(np.abs(got - expect_f64)) / np.max(np.abs(expect_f64)) < 1e-5


# --------------------------------------------------------------- megakernel

FUSED = FusedBackend(interpret=True)


@pytest.mark.parametrize("mode", ["fast", "accu"])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_fused_launch_count_real(rng, dtype, mode):
    """Acceptance: the megakernel traces a real emulated GEMM — fast AND
    accu (the scaling pass is pallas-free) — to exactly ONE `pallas_call`,
    matching `kernel_launch_count(..., fused=True)`, and stays bitwise
    identical to the 4-launch kernel path."""
    a, b = _operands(rng, dtype)
    plan = _garner_plan(dtype, mode)
    want = perfmodel.kernel_launch_count(5, "real", fused=True)
    assert want == 1
    assert certify_launch_count(
        want, lambda x, y: execute_plan(plan, x, y, FUSED), a, b
    ) == []
    np.testing.assert_array_equal(
        np.asarray(execute_plan(plan, a, b, FUSED)),
        np.asarray(execute_plan(plan, a, b, BATCHED)),
    )


@pytest.mark.parametrize("formulation", ["karatsuba", "block_a", "block_b"])
@pytest.mark.parametrize("mode", ["fast", "accu"])
@pytest.mark.parametrize("dtype", [np.complex64, np.complex128])
def test_fused_launch_count_complex(rng, dtype, mode, formulation):
    """Acceptance: one `pallas_call` for a complex emulated GEMM on every
    Fig. 1 formulation x mode, bitwise identical to the kernel path (the
    block embeddings ride the real megakernel on embedded operands; the
    Karatsuba megakernel fuses cast + D/E/F + both Garner epilogues)."""
    a, b = _operands(rng, dtype)
    plan = _garner_plan(dtype, mode, formulation, n_moduli=4)
    want = perfmodel.kernel_launch_count(4, formulation, fused=True)
    assert want == 1
    assert certify_launch_count(
        want, lambda x, y: execute_plan(plan, x, y, FUSED), a, b
    ) == []
    np.testing.assert_array_equal(
        np.asarray(execute_plan(plan, a, b, FUSED)),
        np.asarray(execute_plan(plan, a, b, BATCHED)),
    )


@pytest.mark.parametrize("mode", ["fast", "accu"])
@pytest.mark.parametrize("dtype", [np.float32, np.complex64])
def test_fused_prepared_one_launch(rng, dtype, mode):
    """Prepared serving on the megakernel: the pre-cast weight planes feed
    the kernel's B residue inputs directly, so the whole prepared GEMM is
    still ONE launch (vs 3 on the kernel path) and bitwise identical."""
    from repro.core.executor import PreparedOperand, gemm_prepared

    a, b = _operands(rng, dtype)
    keep_raw = mode == "accu"
    wk = PreparedOperand(b, 5, side="right", backend=BATCHED, keep_raw=keep_raw)
    wf = PreparedOperand(b, 5, side="right", backend=FUSED, keep_raw=keep_raw)
    kw = dict(method="garner", mode=mode)
    want_model = perfmodel.kernel_launch_count(
        5, "real" if dtype == np.float32 else "karatsuba",
        fused=True, prepared=True,
    )
    assert want_model == 1
    assert certify_launch_count(
        want_model, lambda x: gemm_prepared(wf, x, backend=FUSED, **kw), a
    ) == []
    np.testing.assert_array_equal(
        np.asarray(gemm_prepared(wf, a, backend=FUSED, **kw)),
        np.asarray(gemm_prepared(wk, a, backend=BATCHED, **kw)),
    )


def test_fused_chunked_k_one_launch(rng, monkeypatch):
    """K-chunking moves INSIDE the megakernel grid (k innermost = Pallas
    double-buffers the block fetches): the host carry loop of the kernel
    path collapses into one launch, still bitwise identical — the in-kernel
    chunk reduction produces the same canonical residues as the host
    carries."""
    import repro.core.executor as executor

    a, b = _operands(rng, np.float32, k=160)
    plan = _garner_plan(np.float32)
    ca, cb = _operands(rng, np.complex64, k=160)
    cplan = _garner_plan(np.complex64, formulation="karatsuba")
    whole = np.asarray(execute_plan(plan, a, b, BATCHED))
    cwhole = np.asarray(execute_plan(cplan, ca, cb, BATCHED))

    monkeypatch.setattr(executor, "K_CHUNK_LIMIT", 64)
    np.testing.assert_array_equal(
        whole, np.asarray(execute_plan(plan, a, b, FUSED))
    )
    np.testing.assert_array_equal(
        cwhole, np.asarray(execute_plan(cplan, ca, cb, FUSED))
    )
    want = perfmodel.kernel_launch_count(5, "real", n_chunks=3, fused=True)
    assert want == 1
    assert certify_launch_count(
        want, lambda x, y: execute_plan(plan, x, y, FUSED), a, b
    ) == []


def test_fused_n_block_launch_per_block(rng):
    """Output-column blocking still fans out one launch PER BLOCK (the
    n_blocks factor of `kernel_launch_count`), each block a full megakernel,
    bitwise identical to the blocked kernel path."""
    a, b = _operands(rng, np.float32)
    plan = _garner_plan(np.float32, n_block=8)  # FAST_N=24 -> 3 blocks
    want = perfmodel.kernel_launch_count(5, "real", fused=True, n_blocks=3)
    assert want == 3
    assert certify_launch_count(
        want, lambda x, y: execute_plan(plan, x, y, FUSED), a, b
    ) == []
    np.testing.assert_array_equal(
        np.asarray(execute_plan(plan, a, b, FUSED)),
        np.asarray(execute_plan(plan, a, b, BATCHED)),
    )
