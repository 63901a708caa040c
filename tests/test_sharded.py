"""`GemmPolicy(execution="sharded")`: the residue pipeline over the mesh.

What this file guarantees (tests/test_linalg.py covers the single-device
policy stack; this file covers its distribution):

  * sharded execution is **bitwise identical to execution="kernel"** — on a
    1-device mesh (the acceptance criterion) and, because the partial
    reconstruction combines in the exact order-independent int32 split of
    `core/crt.partial_split`, on EVERY mesh shape (data x model x residue),
    for {f32, f64, c64, c128} x {fast, accu} x all three complex
    formulations and under output-column blocking;
  * the only cross-device traffic is the psum of the reconstructed output's
    exact partial planes — **no int8 residue array appears in any
    collective** (asserted against the traced jaxpr);
  * the mesh/axis plumbing: `use_mesh` / `use_policy(mesh=...)` thread-local
    defaults, `shard_axes` overrides, `resolve_gemm_axes` fallbacks, and
    the serve/train-facing model path (a model under a sharded ambient
    policy generates the same tokens as under the kernel policy, and
    `jax.grad` through the sharded custom VJP matches the kernel VJP).

Multi-device cases run on whatever `jax.devices()` offers and skip
otherwise; CI's multi-device job forces 8 host devices
(XLA_FLAGS=--xla_force_host_platform_device_count=8) so the full matrix
runs there.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from conftest import FAST_K, FAST_M, FAST_N, phi_matrix
import repro
from repro import linalg
from repro.core import GemmPolicy
from repro.core.policy import BACKEND_FOR_DTYPE, policy_matmul, prepare_weights
from repro.distributed.sharding import (
    GemmShardAxes,
    residue_plane_specs,
    resolve_gemm_axes,
)
from repro.analysis import CollectiveSafetyPass, collect_collectives
from repro.launch.mesh import make_mesh

M, K, N = FAST_M, FAST_K, FAST_N
DTYPES = [np.float32, np.float64, np.complex64, np.complex128]
N_MODULI = {"float32": 5, "float64": 6, "complex64": 5, "complex128": 6}


def _mesh(data=1, model=1, residue=1):
    need = data * model * residue
    if len(jax.devices()) < need:
        pytest.skip(f"needs {need} devices, have {len(jax.devices())}")
    return make_mesh((data, model, residue), ("data", "model", "residue"))


def _policy(dtype, execution, **kw):
    name = np.dtype(dtype).name
    kw.setdefault("n_moduli", N_MODULI[name])
    kw.setdefault("interpret", True)
    return GemmPolicy(backend=BACKEND_FOR_DTYPE[name], execution=execution, **kw)


def _operands(rng, dtype, m=M, n=N):
    x = jnp.asarray(phi_matrix(rng, (m, K), 0.5, dtype))
    w = jnp.asarray(phi_matrix(rng, (K, n), 0.5, dtype))
    return x, w


# ================================================= parity: 1-device mesh


@pytest.mark.parametrize("mode", ["fast", "accu"])
@pytest.mark.parametrize("dtype", DTYPES)
def test_sharded_bitwise_kernel_1device(rng, dtype, mode):
    """Acceptance: on a 1-device mesh the sharded execution is bitwise
    identical to execution='kernel' for every dtype x mode."""
    x, w = _operands(rng, dtype)
    mesh = _mesh(1, 1, 1)
    y_k = np.asarray(policy_matmul(x, w, _policy(dtype, "kernel", mode=mode)))
    y_s = np.asarray(
        policy_matmul(x, w, _policy(dtype, "sharded", mode=mode, mesh=mesh))
    )
    np.testing.assert_array_equal(y_k, y_s)


@pytest.mark.parametrize("mode", ["fast", "accu"])
@pytest.mark.parametrize("formulation", ["karatsuba", "block_a", "block_b"])
def test_sharded_formulations_bitwise(rng, formulation, mode):
    """All three Fig. 1 complex strategies x both modes compose through the
    sharded worker (the block embeddings from its dynamic-modulus
    residue_matmul, the fused-Karatsuba kernel from the chunk carry)."""
    x, w = _operands(rng, np.complex64)
    residue = 2 if len(jax.devices()) >= 2 else 1
    mesh = _mesh(1, 1, residue)
    y_k = np.asarray(
        policy_matmul(
            x, w,
            _policy(np.complex64, "kernel", formulation=formulation, mode=mode),
        )
    )
    y_s = np.asarray(
        policy_matmul(
            x, w,
            _policy(np.complex64, "sharded", formulation=formulation,
                    mode=mode, mesh=mesh),
        )
    )
    np.testing.assert_array_equal(y_k, y_s)


# ============================================ parity: multi-device meshes


@pytest.mark.parametrize(
    "meshdims", [(2, 1, 1), (1, 2, 1), (1, 1, 2), (2, 2, 2), (1, 1, 8)]
)
@pytest.mark.parametrize("dtype", DTYPES)
def test_sharded_multi_mesh_bitwise(rng, dtype, meshdims):
    """The falsifiable tentpole claim: residue arithmetic is exact and the
    partial combine is order-independent, so EVERY mesh shape reproduces the
    1-device kernel output bit for bit — residue-sharded (N=5/6 planes over
    2 or 8 shards exercises the zero-plane padding), m/n-sharded, and both."""
    x, w = _operands(rng, dtype)
    mesh = _mesh(*meshdims)
    y_k = np.asarray(policy_matmul(x, w, _policy(dtype, "kernel")))
    y_s = np.asarray(policy_matmul(x, w, _policy(dtype, "sharded", mesh=mesh)))
    np.testing.assert_array_equal(y_k, y_s)


def test_sharded_accu_multi_mesh_bitwise(rng):
    """Accurate mode across a (2, 2, 2) mesh: the pmax-combined bound maxima
    reproduce the global exponents exactly (int32 pmax is exact)."""
    mesh = _mesh(2, 2, 2)
    for dtype in (np.float32, np.complex128):
        x, w = _operands(rng, dtype)
        y_k = np.asarray(policy_matmul(x, w, _policy(dtype, "kernel", mode="accu")))
        y_s = np.asarray(
            policy_matmul(x, w, _policy(dtype, "sharded", mode="accu", mesh=mesh))
        )
        np.testing.assert_array_equal(y_k, y_s)


def test_sharded_n_block_bitwise(rng):
    """Output-column blocking under sharding: each block combines with its
    own psum, and the concatenated blocks still match the kernel path."""
    residue = min(2, len(jax.devices()))
    mesh = _mesh(1, 1, residue)
    x, w = _operands(rng, np.float32)
    y_k = np.asarray(policy_matmul(x, w, _policy(np.float32, "kernel", n_block=8)))
    y_s = np.asarray(
        policy_matmul(x, w, _policy(np.float32, "sharded", n_block=8, mesh=mesh))
    )
    np.testing.assert_array_equal(y_k, y_s)


def test_sharded_indivisible_dims_drop_to_replicated(rng):
    """m/n that don't divide their mesh axes drop to replicated (the
    parameter-rule convention) instead of failing shard_map."""
    mesh = _mesh(2, 2, 2)
    x, w = _operands(rng, np.float32, m=M + 1, n=N + 1)  # 33, 25: odd
    y_k = np.asarray(policy_matmul(x, w, _policy(np.float32, "kernel")))
    y_s = np.asarray(policy_matmul(x, w, _policy(np.float32, "sharded", mesh=mesh)))
    np.testing.assert_array_equal(y_k, y_s)


def test_sharded_reference_inner_bitwise(rng):
    """The debuggable flavour: a ShardedBackend wrapping the jnp reference
    backend (no Pallas) runs the worker's dynamic-modulus f64 product and
    Karatsuba paths and still bit-matches the unsharded reference run."""
    from repro.core.executor import REFERENCE, run_plan
    from repro.core.plan import make_plan
    from repro.distributed.sharded_gemm import ShardedBackend

    mesh = _mesh(1, 1, 2)  # residue sharding is what exercises the dyn ops
    for dtype in (np.float32, np.complex64):
        x, w = _operands(rng, dtype)
        formulation = (
            "karatsuba" if np.issubdtype(dtype, np.complexfloating) else None
        )
        plan = make_plan(
            dtype, n_moduli=5, method="garner", formulation=formulation
        )
        want = np.asarray(run_plan(plan, x, w, REFERENCE))
        got = np.asarray(
            ShardedBackend(REFERENCE, mesh).run_plan(plan, x, w)
        )
        np.testing.assert_array_equal(want, got)


# ==================================================== collective hygiene


def test_no_int8_crosses_the_mesh(rng):
    """The distribution contract: the ONLY communicated arrays are the
    exact int32 partial-reconstruction planes (and int32 bound maxima in accu
    mode) — never the int8 residue planes.  Certified by the shared
    `repro.analysis.CollectiveSafetyPass` (which the analysis CLI also runs
    on every matrix row in CI)."""
    mesh = _mesh(1, 1, 2)
    x, w = _operands(rng, np.complex64)
    for mode in ("fast", "accu"):
        pol = _policy(np.complex64, "sharded", mode=mode, mesh=mesh)
        jaxpr = jax.make_jaxpr(lambda a, b: policy_matmul(a, b, pol))(x, w)
        findings = CollectiveSafetyPass().run(jaxpr)
        assert findings == [], [str(f) for f in findings]
        colls = collect_collectives(jaxpr)
        assert colls, "sharded residue execution must communicate via psum"
        # the payload is the exact int32 partial planes
        assert any(
            name == "psum" and any(dt == jnp.int32 for dt in dtypes)
            for name, dtypes in colls
        )
    # and the same invariant on the compiled (SPMD-partitioned) HLO: no
    # collective op touches an s8 array
    pol = _policy(np.complex64, "sharded", mesh=mesh)
    hlo = (
        jax.jit(lambda a, b: policy_matmul(a, b, pol)).lower(x, w)
        .compile().as_text()
    )
    coll_lines = [
        ln for ln in hlo.splitlines()
        if any(
            f"{c}(" in ln or f"{c}-start(" in ln
            for c in ("all-reduce", "all-gather", "all-to-all",
                      "collective-permute", "reduce-scatter")
        )
    ]
    assert coll_lines, "partitioned HLO should contain the output psum"
    for ln in coll_lines:
        assert "s8[" not in ln, f"int8 in compiled collective: {ln.strip()}"


# ========================================================= differentiation


def test_sharded_grad_matches_kernel(rng):
    """jax.grad through the sharded custom VJP (cotangents are sharded
    emulated GEMMs too) matches the kernel execution bitwise."""
    residue = min(2, len(jax.devices()))
    mesh = _mesh(1, 1, residue)
    x, w = _operands(rng, np.float32)

    def loss(pol):
        return lambda a, b: jnp.sum(linalg.matmul(a, b, policy=pol) ** 2)

    gk = jax.grad(loss(_policy(np.float32, "kernel")), argnums=(0, 1))(x, w)
    gs = jax.grad(
        loss(_policy(np.float32, "sharded", mesh=mesh)), argnums=(0, 1)
    )(x, w)
    for a, b in zip(gk, gs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ============================================== model / serve / train route


def test_sharded_model_generates_like_kernel(rng):
    """The drop-in route: a model built under a sharded ambient policy
    (ModelConfig pins it) serves the same tokens as under the kernel policy
    — one use_policy scope distributes every matmul in the model."""
    from repro.models import Model, ModelConfig
    from repro.serve.engine import ServeEngine

    residue = min(2, len(jax.devices()))
    mesh = _mesh(1, 1, residue)
    kw = dict(
        name="tiny-sharded", n_layers=1, d_model=32, vocab=64, n_heads=2,
        n_kv_heads=1, head_dim=16, d_ff=64, dtype="float32",
    )
    toks = {}
    for execution in ("kernel", "sharded"):
        pol = GemmPolicy(
            backend="ozaki2_f32", n_moduli=6, execution=execution,
            interpret=True, mesh=mesh if execution == "sharded" else None,
        )
        with repro.use_policy(pol):
            cfg = ModelConfig(**kw)
        assert cfg.gemm_policy == pol
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        eng = ServeEngine(model, params, cache_len=8, batch_size=1)
        batch = {"tokens": jnp.asarray([[3, 1, 4, 1]], jnp.int32)}
        toks[execution] = np.asarray(eng.generate(batch, max_new_tokens=2))
    np.testing.assert_array_equal(toks["kernel"], toks["sharded"])


# =============================================== mesh/axis resolution API


def test_sharded_needs_a_mesh(rng):
    x, w = _operands(rng, np.float32)
    pol = _policy(np.float32, "sharded")
    with pytest.raises(ValueError, match="needs a mesh"):
        policy_matmul(x, w, pol)


def test_use_mesh_threadlocal_default(rng):
    """mesh=None resolves the thread-local `use_mesh` default at trace time;
    `use_policy(policy, mesh=...)` scopes both in one statement."""
    mesh = _mesh(1, 1, 1)
    x, w = _operands(rng, np.float32)
    y_k = np.asarray(policy_matmul(x, w, _policy(np.float32, "kernel")))
    assert repro.current_mesh() is None
    with repro.use_mesh(mesh):
        assert repro.current_mesh() is mesh
        y_s = np.asarray(policy_matmul(x, w, _policy(np.float32, "sharded")))
    assert repro.current_mesh() is None
    np.testing.assert_array_equal(y_k, y_s)
    with repro.use_policy(_policy(np.float32, "sharded"), mesh=mesh):
        assert repro.current_mesh() is mesh
        y_s2 = np.asarray(linalg.matmul(x, w))
    np.testing.assert_array_equal(y_k, y_s2)
    with pytest.raises(TypeError):
        with repro.use_mesh("not a mesh"):
            pass


def test_matmul_jit_resolves_ambient_mesh_before_cache(rng):
    """Regression: matmul_jit caches on (shapes, policy) — a mesh-less
    sharded policy must fold the ambient use_mesh mesh into the policy
    BEFORE jit, or the second scope would silently reuse the first mesh
    from the cache (wrong devices, no error)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    x, w = _operands(rng, np.float32)
    pol = _policy(np.float32, "sharded")
    mesh1 = make_mesh((1, 1, 2), ("data", "model", "residue"))
    mesh2 = make_mesh((1, 1, 4), ("data", "model", "residue"))
    with repro.use_mesh(mesh1):
        y1 = linalg.matmul_jit(x, w, policy=pol)
    with repro.use_mesh(mesh2):
        y2 = linalg.matmul_jit(x, w, policy=pol)
    assert {d.id for d in y2.devices()} != {d.id for d in y1.devices()}
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


def test_resolve_gemm_axes_rules():
    mesh = _mesh(1, 1, 1)
    axes = resolve_gemm_axes(mesh)
    assert axes == GemmShardAxes(residue="residue", m="data", n="model")
    # no residue axis: fall back to model, which then can't also carry n
    mesh2 = make_mesh((1, 1), ("data", "model"))
    assert resolve_gemm_axes(mesh2) == GemmShardAxes(
        residue="model", m="data", n=None
    )
    # size-aware m/n: indivisible dims drop to replicated
    assert resolve_gemm_axes(mesh, m=33, n=24).m == (
        "data" if mesh.shape["data"] == 1 else None
    )
    # overrides taken verbatim, validated against the mesh
    assert resolve_gemm_axes(mesh2, overrides=(None, None, "model")) == (
        GemmShardAxes(residue=None, m=None, n="model")
    )
    with pytest.raises(ValueError, match="not on mesh"):
        resolve_gemm_axes(mesh2, overrides=("residue", None, None))
    # the spec table spells the design: int8 stacks shard planes, the psum
    # payload and output never carry the residue axis
    specs = residue_plane_specs(resolve_gemm_axes(mesh))
    assert specs["a_residues"][0] == "residue"
    assert "residue" not in tuple(specs["partial"]) + tuple(specs["out"])


def test_sharded_policy_is_hashable_and_jit_static(rng):
    mesh = _mesh(1, 1, 1)
    pol = _policy(np.float32, "sharded", mesh=mesh)
    assert hash(pol) == hash(dataclasses.replace(pol))
    x, w = _operands(rng, np.float32)
    y = np.asarray(linalg.matmul_jit(x, w, policy=pol))  # policy as jit static
    y_k = np.asarray(policy_matmul(x, w, _policy(np.float32, "kernel")))
    np.testing.assert_array_equal(y, y_k)


def test_prepared_and_sharded_raise(rng):
    """Prepared weights meeting a sharded execution fail FAST with a
    NotImplementedError that names the remediation (serve on 'kernel' /
    'fused' outside a mesh, or pass raw weights) — not a deep generic
    failure.  The fused execution inside a mesh scope resolves to the same
    sharded pipeline, so it must refuse identically."""
    mesh = _mesh(1, 1, 1)
    x, w = _operands(rng, np.float32)
    kpol = _policy(np.float32, "kernel")
    spol = _policy(np.float32, "sharded", mesh=mesh)
    prep = prepare_weights({"w": w}, kpol)["w"]
    with pytest.raises(NotImplementedError, match="execution='kernel'"):
        policy_matmul(x, prep, spol)
    with pytest.raises(NotImplementedError, match="execution='kernel'"):
        prepare_weights({"w": w}, spol)
    fpol = _policy(np.float32, "fused", mesh=mesh)
    with pytest.raises(NotImplementedError, match="mesh"):
        policy_matmul(x, prep, fpol)
    with pytest.raises(NotImplementedError, match="mesh"):
        prepare_weights({"w": w}, fpol)
    # NotImplementedError is not a ValueError: callers that caught the old
    # generic error by type must not silently swallow the new one
    assert not issubclass(NotImplementedError, ValueError)


def test_sharded_plan_prices_communication():
    """plan_for consults the perfmodel's sharded communication term and the
    per-shard shapes, so 'auto' selections model what each shard runs."""
    from repro.core import perfmodel

    mesh = _mesh(1, 1, 1)
    pol = _policy(np.complex64, "sharded", mesh=mesh, formulation="auto")
    plan = pol.plan_for(M, K, N)  # resolves without error on the tiny mesh
    assert plan.formulation in ("karatsuba", "block_a", "block_b")
    # the comm term itself: zero on one shard, grows with the part count
    assert perfmodel.sharded_comm_time_s(256, 256, 8, 1) == 0.0
    t2 = perfmodel.sharded_comm_time_s(256, 256, 8, 2)
    t8 = perfmodel.sharded_comm_time_s(256, 256, 8, 8)
    assert t8 > t2 > perfmodel.COLLECTIVE_LAUNCH_S
    parts = perfmodel.crt_partial_parts(8)
    assert parts >= 2  # ~64-bit weights split into >= 2 exact int32 parts


# ======================================== parity: the fused megakernel


@pytest.mark.parametrize("mode", ["fast", "accu"])
@pytest.mark.parametrize("dtype", DTYPES)
def test_fused_bitwise_kernel_single_device(rng, dtype, mode):
    """Acceptance: execution='fused' (no mesh — the plain megakernel) is
    bitwise identical to execution='kernel' for every dtype x mode at the
    policy entry point."""
    x, w = _operands(rng, dtype)
    y_k = np.asarray(policy_matmul(x, w, _policy(dtype, "kernel", mode=mode)))
    y_f = np.asarray(policy_matmul(x, w, _policy(dtype, "fused", mode=mode)))
    np.testing.assert_array_equal(y_k, y_f)


@pytest.mark.parametrize(
    "meshdims", [(2, 1, 1), (1, 2, 1), (1, 1, 2), (2, 2, 2), (1, 1, 8)]
)
@pytest.mark.parametrize("dtype", DTYPES)
def test_fused_multi_mesh_bitwise(rng, dtype, meshdims):
    """The megakernel under every mesh shape reproduces the 1-device kernel
    output bit for bit: m/n-sharded meshes run the fused worker (one launch
    per shard), residue-sharded meshes fall back to the composed worker
    with the two-phase deferred psum — both produce the same canonical
    residues, hence the same bits."""
    x, w = _operands(rng, dtype)
    mesh = _mesh(*meshdims)
    y_k = np.asarray(policy_matmul(x, w, _policy(dtype, "kernel")))
    y_f = np.asarray(policy_matmul(x, w, _policy(dtype, "fused", mesh=mesh)))
    np.testing.assert_array_equal(y_k, y_f)


def test_fused_worker_engages_on_mn_mesh(rng):
    """Structural check behind the mesh parity: on an m/n-only mesh the
    sharded wrapper delegates to the fused worker — the traced program holds
    exactly ONE `pallas_call` — while a residue-sharded mesh falls back to
    the composed worker (multiple launches, two-phase psum), since the fused
    Garner epilogue needs the full compile-time-static modulus set."""
    from repro.analysis import count_pallas_calls
    from repro.kernels import FusedBackend, KernelBackend
    from repro.distributed.sharded_gemm import ShardedBackend

    x, w = _operands(rng, np.float32)
    mesh_mn = _mesh(1, 2, 1)
    assert ShardedBackend(FusedBackend(True), mesh_mn, None).megakernel
    assert not ShardedBackend(KernelBackend(True), mesh_mn, None).megakernel
    got_mn = count_pallas_calls(
        lambda a, b: policy_matmul(
            a, b, _policy(np.float32, "fused", mesh=mesh_mn)
        ),
        x, w,
    )
    assert got_mn == 1
    if len(jax.devices()) >= 2:
        mesh_r = _mesh(1, 1, 2)
        got_r = count_pallas_calls(
            lambda a, b: policy_matmul(
                a, b, _policy(np.float32, "fused", mesh=mesh_r)
            ),
            x, w,
        )
        assert got_r > 1  # composed fallback: per-stage launches
