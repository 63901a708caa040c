"""The execution-policy redesign: `repro.linalg` + `use_policy` + shims.

What this file guarantees:

  * `policy_matmul` / `linalg.matmul` with ``execution="kernel"`` runs the
    modulus-batched Pallas pipeline (asserted by the traced `pallas_call`
    count, including the 3-launch prepared-weight path) and is
    - bitwise-identical to ``execution="per_modulus_kernel"`` for every
      dtype x mode x prepared combination (kernel-path parity), and
    - bitwise-identical to ``execution="reference"`` for the f32-grade
      dtypes (f32/c64): the kernel path casts through f32 and reconstructs
      in double-single, which the f32 output rounding absorbs exactly; the
      f64-grade dtypes agree to the kernel path's f32-grade band instead.
  * `use_policy` scoping: thread-local, nestable, captured at config
    construction (ModelConfig) and at trace time (linalg.matmul).
  * the four legacy `ozaki2_*` entry points warn `DeprecationWarning` and
    still agree bitwise with `linalg.matmul` under the equivalent policy.
  * `prepare_weights` rewrites "w" leaves reached through list/tuple
    bundles (scanned layer groups) and casts with the policy's execution
    backend, so prepared serving is bit-identical on the kernel path;
    `ServeEngine(prepare=True, prepared_dir=...)` restores the persisted
    residue planes bitwise instead of re-preparing.
"""
import dataclasses
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from conftest import FAST_K, FAST_M, FAST_N, phi_matrix
import repro
from repro import linalg
from repro.core import GemmPolicy, PreparedOperand, perfmodel
from repro.core.policy import BACKEND_FOR_DTYPE, policy_matmul, prepare_weights
from repro.kernels.common import count_pallas_launches

M, K, N = FAST_M, FAST_K, FAST_N

DTYPES = [np.float32, np.float64, np.complex64, np.complex128]
# small moduli counts keep the interpret-mode sweeps fast; parity is
# independent of N
N_MODULI = {"float32": 5, "float64": 6, "complex64": 5, "complex128": 6}
F32_GRADE = ("float32", "complex64")


def _policy(dtype, execution, **kw):
    name = np.dtype(dtype).name
    kw.setdefault("n_moduli", N_MODULI[name])
    kw.setdefault("interpret", True)
    return GemmPolicy(backend=BACKEND_FOR_DTYPE[name], execution=execution, **kw)


def _operands(rng, dtype):
    x = jnp.asarray(phi_matrix(rng, (M, K), 0.5, dtype))
    w = jnp.asarray(phi_matrix(rng, (K, N), 0.5, dtype))
    return x, w


# ===================================================== execution parity


@pytest.mark.parametrize("mode", ["fast", "accu"])
@pytest.mark.parametrize("dtype", DTYPES)
def test_policy_execution_parity(rng, dtype, mode):
    """Tentpole: the execution axis selects the backend without changing the
    numbers — batched kernels == per-modulus kernels bitwise everywhere, and
    == the jnp reference bitwise at f32 grade."""
    x, w = _operands(rng, dtype)
    ys = {
        ex: np.asarray(policy_matmul(x, w, _policy(dtype, ex, mode=mode)))
        for ex in ("reference", "kernel", "per_modulus_kernel")
    }
    np.testing.assert_array_equal(ys["kernel"], ys["per_modulus_kernel"])
    name = np.dtype(dtype).name
    if name in F32_GRADE:
        np.testing.assert_array_equal(ys["kernel"], ys["reference"])
    else:
        # the kernel path quantizes through f32, so f64-grade operands agree
        # with the f64 reference only to the f32-grade band
        scale = np.max(np.abs(ys["reference"]))
        assert np.max(np.abs(ys["kernel"] - ys["reference"])) / scale < 1e-6


@pytest.mark.parametrize("execution", ["reference", "kernel"])
@pytest.mark.parametrize("dtype", DTYPES)
def test_policy_prepared_parity(rng, dtype, execution):
    """`prepare_weights` casts with the *selected* execution backend, so the
    prepared fast path is bit-identical to the unprepared run per execution
    (kernel path included — its f32 cast must be baked into the residues)."""
    x, w = _operands(rng, dtype)
    pol = _policy(dtype, execution)
    direct = np.asarray(policy_matmul(x, w, pol))
    tree = prepare_weights({"w": w}, pol)
    assert isinstance(tree["w"], PreparedOperand)
    prepped = np.asarray(policy_matmul(x, tree["w"], pol))
    np.testing.assert_array_equal(direct, prepped)


@pytest.mark.parametrize("execution", ["reference", "kernel"])
@pytest.mark.parametrize("dtype", [np.float32, np.complex64])
def test_policy_prepared_accu_parity(rng, dtype, execution):
    """ROADMAP follow-up from PR 3: accu-mode preparation stores the
    per-column 7-bit bound alongside the residue planes (and the raw
    operand — the coupled exponents force a per-call cast) and stays
    bitwise identical to the unprepared accu run on both backends."""
    x, w = _operands(rng, dtype)
    pol = _policy(dtype, execution, mode="accu", n_moduli=6)
    direct = np.asarray(policy_matmul(x, w, pol))
    tree = prepare_weights({"w": w}, pol)
    prep = tree["w"]
    assert isinstance(prep, PreparedOperand)
    assert prep.raw is not None and prep.bound[0].dtype == jnp.int8
    prepped = np.asarray(policy_matmul(x, prep, pol))
    np.testing.assert_array_equal(direct, prepped)


def test_policy_prepared_accu_requires_raw(rng):
    """A fast-prepared operand (no raw retained) used under an accu policy
    fails loudly with re-preparation guidance, never silently degrades."""
    x, w = _operands(rng, np.float32)
    fast_pol = _policy(np.float32, "kernel", n_moduli=6)
    prep = prepare_weights({"w": w}, fast_pol)["w"]
    assert prep.raw is None  # fast preparation keeps the memory win
    accu_pol = _policy(np.float32, "kernel", mode="accu", n_moduli=6)
    with pytest.raises(ValueError, match="raw operand"):
        policy_matmul(x, prep, accu_pol)


def test_policy_prepared_auto_formulation_parity(rng):
    """Regression: gemm_prepared must charge the perfmodel the executing
    backend's real launch capabilities, or formulation='auto' can pick a
    different Fig. 1 strategy for the prepared run than the unprepared run
    it must bit-match (e.g. block_a vs karatsuba on the batched kernels)."""
    x = jnp.asarray(phi_matrix(rng, (64, 64), 0.5, np.complex64))
    w = jnp.asarray(phi_matrix(rng, (64, 64), 0.5, np.complex64))
    for execution in ("reference", "kernel"):
        pol = _policy(np.complex64, execution, formulation="auto")
        direct = np.asarray(policy_matmul(x, w, pol))
        prep = prepare_weights({"w": w}, pol)["w"]
        prepped = np.asarray(policy_matmul(x, prep, pol))
        np.testing.assert_array_equal(direct, prepped)


def test_policy_out_dtype_axis(rng):
    """out_dtype is a policy axis: f64-grade output from f32 operands."""
    x, w = _operands(rng, np.float32)
    pol = _policy(np.float32, "reference", n_moduli=8, out_dtype="float64")
    y = policy_matmul(x, w, pol)
    assert y.dtype == jnp.float64
    ref = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
    assert np.max(np.abs(np.asarray(y) - ref)) / np.max(np.abs(ref)) < 1e-7


# ===================================================== launch counting


def test_policy_kernel_launch_counts(rng):
    """Acceptance: the policy path really runs the batched Pallas pipeline —
    4 launches per GEMM (cast, cast, product, reconstruct) at any N, 3 with
    a prepared weight, 3+N on the per-modulus parity path."""
    x, w = _operands(rng, np.float32)
    pol = _policy(np.float32, "kernel")
    got = count_pallas_launches(lambda a, b: policy_matmul(a, b, pol), x, w)
    assert got == perfmodel.kernel_launch_count(pol.n_moduli, "real") == 4

    prep = prepare_weights({"w": w}, pol)["w"]
    got_prep = count_pallas_launches(
        lambda a: policy_matmul(a, prep, pol), x
    )
    assert (
        got_prep
        == perfmodel.kernel_launch_count(pol.n_moduli, "real", prepared=True)
        == 3
    )

    pm = _policy(np.float32, "per_modulus_kernel")
    got_pm = count_pallas_launches(lambda a, b: policy_matmul(a, b, pm), x, w)
    assert got_pm == perfmodel.kernel_launch_count(
        pm.n_moduli, "real", modulus_batched=False
    ) == 3 + pm.n_moduli


def test_acceptance_c64_kernel_drop_in(rng):
    """The ISSUE acceptance scenario verbatim: `repro.linalg.matmul` under
    `use_policy(GemmPolicy(backend="ozaki2_c64", execution="kernel"))` runs
    the batched Pallas path (jaxpr launch count) and is bitwise-identical to
    execution="reference" in interpret mode."""
    x, w = _operands(rng, np.complex64)
    kpol = GemmPolicy(backend="ozaki2_c64", execution="kernel", interpret=True)
    with repro.use_policy(kpol):
        y_kernel = np.asarray(linalg.matmul(x, w))
        launches = count_pallas_launches(linalg.matmul, x, w)
    with repro.use_policy(dataclasses.replace(kpol, execution="reference")):
        y_ref = np.asarray(linalg.matmul(x, w))
    assert launches == perfmodel.kernel_launch_count(
        kpol.n_moduli or 7, "karatsuba"
    ) == 4
    np.testing.assert_array_equal(y_kernel, y_ref)
    # and it is numerically a complex128-grade product of the c64 operands
    ref = np.asarray(x, np.complex128) @ np.asarray(w, np.complex128)
    assert np.max(np.abs(y_kernel - ref)) / np.max(np.abs(ref)) < 1e-5


# ===================================================== use_policy scoping


def test_reference_execution_refuses_tpu(monkeypatch):
    """The jnp reference reconstruction is exact only in IEEE f64, which a
    TPU does not have: traced for a TPU it raises instead of rounding."""
    a = jnp.ones((3, 5), jnp.float32)
    b = jnp.ones((5, 7), jnp.float32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="IEEE f64"):
        linalg.sgemm(a, b, policy=GemmPolicy(execution="reference"))


def test_use_policy_scoping():
    assert repro.current_policy() == GemmPolicy()
    p1 = GemmPolicy(backend="ozaki2_f32", n_moduli=6)
    p2 = GemmPolicy(backend="ozaki2_c64", execution="kernel")
    with repro.use_policy(p1):
        assert repro.current_policy() == p1
        with repro.use_policy(p2):
            assert repro.current_policy() == p2
        assert repro.current_policy() == p1
    assert repro.current_policy() == GemmPolicy()
    # backend-name shorthand
    with repro.use_policy("ozaki2_f64") as pol:
        assert pol.backend == "ozaki2_f64"
        assert repro.current_policy() == pol
    with pytest.raises(TypeError):
        with repro.use_policy(42):
            pass


def test_use_policy_restores_on_error():
    try:
        with repro.use_policy("ozaki2_f32"):
            raise RuntimeError("boom")
    except RuntimeError:
        pass
    assert repro.current_policy() == GemmPolicy()


def test_policy_validation():
    with pytest.raises(ValueError):
        GemmPolicy(execution="gpu")
    with pytest.raises(ValueError):
        GemmPolicy(backend="ozaki2_f32", execution="kernel", method="paper")
    with pytest.raises(ValueError):
        GemmPolicy(backend="cublas")
    # method='auto' resolves per execution
    assert GemmPolicy(backend="ozaki2_f32").resolved_method == "paper"
    assert (
        GemmPolicy(backend="ozaki2_f32", execution="kernel").resolved_method
        == "garner"
    )
    # out_dtype spellings normalize into one hashable policy
    assert GemmPolicy(out_dtype=jnp.float64) == GemmPolicy(out_dtype="float64")


def test_model_config_pins_ambient_policy():
    from repro.models import ModelConfig

    kw = dict(name="t", n_layers=1, d_model=8, vocab=16)
    assert ModelConfig(**kw).gemm_policy == GemmPolicy()
    pol = GemmPolicy(backend="ozaki2_f32", n_moduli=6, execution="kernel")
    with repro.use_policy(pol):
        cfg = ModelConfig(**kw)
    assert cfg.gemm_policy == pol
    # pinned: leaving the scope does not unpin
    assert dataclasses.replace(cfg, d_model=16).gemm_policy == pol
    # explicit None re-resolves against the (now empty) scope
    assert dataclasses.replace(cfg, gemm_policy=None).gemm_policy == GemmPolicy()


def test_config_registry_resolves_ambient_policy():
    """Registry configs are import-time objects; get_config/get_reduced must
    re-pin the ambient policy at lookup (explicit overrides still win)."""
    from repro.configs import get_reduced

    pol = GemmPolicy(backend="ozaki2_f32", n_moduli=6, execution="kernel")
    with repro.use_policy(pol):
        assert get_reduced("starcoder2-3b").gemm_policy == pol
        explicit = GemmPolicy(backend="ozaki2_f64")
        assert (
            get_reduced("starcoder2-3b", gemm_policy=explicit).gemm_policy
            == explicit
        )
    assert get_reduced("starcoder2-3b").gemm_policy == GemmPolicy()


# ===================================================== BLAS-shaped wrappers


def test_blas_wrappers_force_compute_dtype(rng):
    x, w = _operands(rng, np.float32)
    # cgemm is the emulated complex64 product whatever the ambient backend
    y = linalg.cgemm(x, w, policy=GemmPolicy(n_moduli=5))
    assert y.dtype == jnp.complex64
    z = linalg.dgemm(x, w, policy=GemmPolicy(n_moduli=6))
    assert z.dtype == jnp.float64
    ref = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
    assert np.max(np.abs(np.asarray(z) - ref)) / np.max(np.abs(ref)) < 1e-4
    s = linalg.sgemm(x, w, policy=GemmPolicy(n_moduli=8))
    assert s.dtype == jnp.float32
    zz = linalg.zgemm(
        *_operands(rng, np.complex128), policy=GemmPolicy(n_moduli=6)
    )
    assert zz.dtype == jnp.complex128


def test_matmul_batched_weight_and_errors(rng):
    xb = jnp.asarray(phi_matrix(rng, (2, M, K), 0.5, np.float32))
    wb = jnp.asarray(phi_matrix(rng, (2, K, N), 0.5, np.float32))
    pol = _policy(np.float32, "reference", n_moduli=8)
    y = linalg.matmul(xb, wb, policy=pol)
    assert y.shape == (2, M, N)
    ref = np.einsum("bmk,bkn->bmn", np.asarray(xb), np.asarray(wb))
    assert np.max(np.abs(np.asarray(y) - ref)) < 1e-4 * np.max(np.abs(ref))
    with pytest.raises(ValueError):
        linalg.matmul(jnp.ones((4,)), jnp.ones((4, 2)), policy=pol)


# ===================================================== legacy shims


def test_legacy_shims_deprecated_and_agree(rng):
    from repro.core import ozaki2_cgemm, ozaki2_gemm
    from repro.kernels import ozaki2_cgemm_kernels, ozaki2_gemm_kernels

    x, w = _operands(rng, np.float64)
    cx, cw = _operands(rng, np.complex128)
    fx, fw = x.astype(jnp.float32), w.astype(jnp.float32)
    c4x, c4w = cx.astype(jnp.complex64), cw.astype(jnp.complex64)

    with pytest.warns(DeprecationWarning, match="ozaki2_gemm is deprecated"):
        legacy = np.asarray(ozaki2_gemm(x, w, 6, "fast"))
    modern = np.asarray(
        linalg.matmul(x, w, policy=GemmPolicy(backend="ozaki2_f64", n_moduli=6))
    )
    np.testing.assert_array_equal(legacy, modern)

    with pytest.warns(DeprecationWarning, match="ozaki2_cgemm is deprecated"):
        legacy = np.asarray(ozaki2_cgemm(cx, cw, 6, "accu", formulation="block_a"))
    modern = np.asarray(
        linalg.matmul(
            cx,
            cw,
            policy=GemmPolicy(
                backend="ozaki2_c128", n_moduli=6, mode="accu",
                formulation="block_a",
            ),
        )
    )
    np.testing.assert_array_equal(legacy, modern)

    with pytest.warns(DeprecationWarning, match="ozaki2_gemm_kernels"):
        legacy = np.asarray(ozaki2_gemm_kernels(fx, fw, n_moduli=5, interpret=True))
    modern = np.asarray(linalg.matmul(fx, fw, policy=_policy(np.float32, "kernel")))
    np.testing.assert_array_equal(legacy, modern)

    with pytest.warns(DeprecationWarning, match="ozaki2_cgemm_kernels"):
        legacy = np.asarray(
            ozaki2_cgemm_kernels(c4x, c4w, n_moduli=5, interpret=True)
        )
    modern = np.asarray(
        linalg.matmul(c4x, c4w, policy=_policy(np.complex64, "kernel"))
    )
    np.testing.assert_array_equal(legacy, modern)


# ===================================================== prepare_weights walk


def test_prepare_weights_scanned_bundles(rng):
    """Regression: "w" values reached through list/tuple nesting (scanned /
    stacked weight bundles) are prepared too, not silently left raw."""
    w2 = jnp.asarray(phi_matrix(rng, (K, N), 0.5, np.float32))
    wstack = jnp.asarray(
        np.stack([phi_matrix(rng, (K, N), 0.5, np.float32) for _ in range(3)])
    )
    pol = _policy(np.float32, "kernel")
    tree = {
        "dense": {"w": w2, "b": jnp.zeros((N,), jnp.float32)},
        "groups": [
            {"attn": {"w": wstack}},
            {"mlp": {"w": (wstack, w2)}},  # the formerly-missed case
        ],
        "meta": {"steps": jnp.arange(3)},
    }
    out = prepare_weights(tree, pol)
    assert isinstance(out["dense"]["w"], PreparedOperand)
    assert isinstance(out["groups"][0]["attn"]["w"], PreparedOperand)
    assert out["groups"][0]["attn"]["w"].residues[0].shape[0] == 3
    tup = out["groups"][1]["mlp"]["w"]
    assert isinstance(tup, tuple) and all(
        isinstance(v, PreparedOperand) for v in tup
    )
    # non-"w" leaves untouched
    assert isinstance(out["dense"]["b"], jnp.ndarray)
    assert isinstance(out["meta"]["steps"], jnp.ndarray)
    # the scanned stack slices per layer exactly like the raw weights
    x = jnp.asarray(phi_matrix(rng, (M, K), 0.5, np.float32))
    sl = jax.tree.map(lambda v: v[1], tup[0])
    got = np.asarray(policy_matmul(x, sl, pol))
    want = np.asarray(policy_matmul(x, wstack[1], pol))
    np.testing.assert_array_equal(got, want)


# ===================================================== serving round trip


def _tiny_engine_cfg(execution):
    from repro.configs import get_reduced

    pol = GemmPolicy(
        backend="ozaki2_f32", n_moduli=6, execution=execution, interpret=True
    )
    with repro.use_policy(pol):
        # gemm_policy=None: the config pins the ambient policy — the
        # context-scoped deployment path the redesign is about
        cfg = dataclasses.replace(
            get_reduced("starcoder2-3b"),
            gemm_policy=None,
            dtype="float32",
            n_layers=1,
        )
    assert cfg.gemm_policy == pol
    return cfg


def test_serve_engine_kernel_prepared_and_restore(rng, tmp_path):
    """Acceptance + satellite: prepared serving on the *kernel* execution is
    bit-transparent, and a second engine restores the persisted residue
    planes (bitwise) instead of re-preparing."""
    from repro.models import Model
    from repro.serve.engine import ServeEngine

    cfg = _tiny_engine_cfg("kernel")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (1, 8)), jnp.int32)
    batch = {"tokens": tokens}
    plain = ServeEngine(model, params, cache_len=16, batch_size=1)
    pdir = str(tmp_path / "prepared")
    prepped = ServeEngine(
        model, params, cache_len=16, batch_size=1, prepare=True,
        prepared_dir=pdir,
    )
    t1 = np.asarray(plain.generate(batch, max_new_tokens=2))
    t2 = np.asarray(prepped.generate(batch, max_new_tokens=2))
    np.testing.assert_array_equal(t1, t2)

    # restart: restores instead of re-preparing, bitwise-equal planes
    restored = ServeEngine(
        model, params, cache_len=16, batch_size=1, prepare=True,
        prepared_dir=pdir,
    )
    leaves1 = jax.tree.leaves(prepped.params)
    leaves2 = jax.tree.leaves(restored.params)
    assert len(leaves1) == len(leaves2)
    prepared_leaf_seen = False
    for a, b in zip(leaves1, leaves2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        prepared_leaf_seen |= np.asarray(a).dtype == np.int8
    assert prepared_leaf_seen  # residue planes actually round-tripped
    t3 = np.asarray(restored.generate(batch, max_new_tokens=2))
    np.testing.assert_array_equal(t1, t3)

    # stale cache: a save from a different policy (here: a reference-cast
    # cache reused under another execution) must be detected and re-prepared
    # loudly, not silently served
    cfg_ref = dataclasses.replace(
        cfg, gemm_policy=dataclasses.replace(cfg.gemm_policy,
                                             execution="reference")
    )
    model_ref = Model(cfg_ref)
    with pytest.warns(UserWarning, match="re-preparing"):
        reprep = ServeEngine(
            model_ref, params, cache_len=16, batch_size=1, prepare=True,
            prepared_dir=pdir,
        )
    # f32 casts agree between backends, so generation still matches
    np.testing.assert_array_equal(
        t1, np.asarray(reprep.generate(batch, max_new_tokens=2))
    )
    # non-prepared leaves (embeddings, norms, biases) do not invalidate the
    # cache: only the weights preparation consumes are fingerprinted
    embed_bumped = dict(params, embed=params["embed"] + 1e-3)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        ServeEngine(
            model_ref, embed_bumped, cache_len=16, batch_size=1, prepare=True,
            prepared_dir=pdir,
        )
    # stale weights: perturbing a prepared "w" leaf must re-prepare, loudly
    jtu = jax.tree_util
    w_bumped = jtu.tree_map_with_path(
        lambda path, a: a + 1e-3 if jtu.keystr(path).endswith("['w']") else a,
        params,
    )
    assert any(
        jtu.keystr(p).endswith("['w']")
        for p, _ in jtu.tree_flatten_with_path(params)[0]
    )
    with pytest.warns(UserWarning, match="re-preparing"):
        ServeEngine(
            model_ref, w_bumped, cache_len=16, batch_size=1, prepare=True,
            prepared_dir=pdir,
        )


def test_serve_engine_c64_kernel_prepared(rng):
    """Acceptance tail: the complex kernel policy is bit-transparent through
    `ServeEngine(prepare=True)` too (tiny 1-layer model, interpret mode)."""
    from repro.models import Model, ModelConfig
    from repro.serve.engine import ServeEngine

    pol = GemmPolicy(
        backend="ozaki2_c64", n_moduli=5, execution="kernel", interpret=True
    )
    with repro.use_policy(pol):
        cfg = ModelConfig(
            name="tiny-c64", n_layers=1, d_model=32, vocab=64,
            n_heads=2, n_kv_heads=1, head_dim=16, d_ff=64,
            dtype="float32",
        )
    assert cfg.gemm_policy == pol
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (1, 4)), jnp.int32)}
    plain = ServeEngine(model, params, cache_len=8, batch_size=1)
    prepped = ServeEngine(model, params, cache_len=8, batch_size=1, prepare=True)
    t1 = np.asarray(plain.generate(batch, max_new_tokens=2))
    t2 = np.asarray(prepped.generate(batch, max_new_tokens=2))
    np.testing.assert_array_equal(t1, t2)


def test_prepared_operand_checkpoint_roundtrip(rng):
    """Direct checkpointer round-trip of real + complex PreparedOperands."""
    import tempfile

    from repro.checkpoint import Checkpointer

    w = jnp.asarray(phi_matrix(rng, (K, N), 0.5, np.complex64))
    tree = {
        "c": PreparedOperand(w, 5, side="right"),
        "r": PreparedOperand(jnp.real(w), 5, side="left"),
    }
    like = jax.eval_shape(lambda: tree)
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        ck.save(3, tree)
        out = ck.restore(3, like)
    for key in ("c", "r"):
        a, b = tree[key], out[key]
        assert (a.side, a.n_moduli, a.n_limbs, a.dtype) == (
            b.side, b.n_moduli, b.n_limbs, b.dtype,
        )
        assert len(a.residues) == len(b.residues)
        np.testing.assert_array_equal(np.asarray(a.e_scale), np.asarray(b.e_scale))
        for ra, rb in zip(a.residues, b.residues):
            np.testing.assert_array_equal(np.asarray(ra), np.asarray(rb))
