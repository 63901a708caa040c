"""Tests for the `repro.analysis` certifier: each jaxpr pass gets a
positive certificate (the real pipeline / boundary case comes back clean)
AND a negative test (a deliberately broken program is flagged), plus
property tests that the chunking machinery always satisfies the bound the
OverflowPass proves, and lint tests on synthetic repos.

The negative programs are raw `lax` constructions on purpose: the library
entry points (`int8_matmul`, `fp8_mod_gemm_batched`, ...) raise ValueError
above their chunk limits, so the only way to put an over-limit dot in a
jaxpr is to bypass them — exactly the regression the passes guard against.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis import (
    CollectiveSafetyPass,
    Finding,
    LaunchCountPass,
    OverflowPass,
    ScanIndexWidthPass,
    certify_launch_count,
    certify_partial_split,
    collect_collectives,
    count_pallas_calls,
    expected_launch_count,
    lint_policy_surface,
    passes_for_backend,
    run_passes,
)
from repro.analysis.jaxprs import count_primitive, iter_eqns, unwrap
from repro.analysis.lint import execution_choices
from repro.core.moduli import K_CHUNK_LIMIT, make_crt_context
from repro.core.policy import EXECUTIONS, GemmPolicy


# ---------------------------------------------------------------------------
# OverflowPass: int8 accumulation bound
# ---------------------------------------------------------------------------

def _int8_dot_jaxpr(k):
    """Raw int8 dot_general of contraction length k (shapes only; traced)."""
    a = jax.ShapeDtypeStruct((2, k), jnp.int8)
    b = jax.ShapeDtypeStruct((k, 3), jnp.int8)
    return jax.make_jaxpr(
        lambda x, y: jax.lax.dot_general(
            x, y, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
    )(a, b)


def test_overflow_int8_at_limit_certifies():
    assert OverflowPass().run(_int8_dot_jaxpr(K_CHUNK_LIMIT)) == []


def test_overflow_int8_beyond_limit_flagged():
    findings = OverflowPass().run(_int8_dot_jaxpr(K_CHUNK_LIMIT + 1))
    assert len(findings) == 1
    f = findings[0]
    assert f.pass_name == "overflow" and f.primitive == "dot_general"
    assert "K_CHUNK_LIMIT" in f.message
    assert "dot_general" in str(f)


def test_overflow_float_dots_never_flagged():
    """Ordinary float compute is out of scope — no bound is provable."""
    a = jax.ShapeDtypeStruct((2, K_CHUNK_LIMIT * 4), jnp.float32)
    b = jax.ShapeDtypeStruct((K_CHUNK_LIMIT * 4, 3), jnp.float32)
    jaxpr = jax.make_jaxpr(jnp.matmul)(a, b)
    assert OverflowPass().run(jaxpr) == []


def test_overflow_sees_through_pallas_grid(rng):
    """Inside a pallas kernel the effective K is per-block contraction x the
    innermost grid axis; the kernel launch at the engine's exact limit must
    certify (the grid multiplies a small block dot up to K_CHUNK_LIMIT)."""
    from repro.core.executor import execute_plan
    from repro.kernels import KernelBackend

    pol = GemmPolicy(backend="ozaki2_f32", n_moduli=4, execution="kernel",
                     interpret=True)
    plan = pol.plan_for(8, 256, 8)
    a = jnp.asarray(rng.standard_normal((8, 256)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((256, 8)), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda x, y: execute_plan(plan, x, y, KernelBackend(interpret=True))
    )(a, b)
    assert OverflowPass().run(jaxpr) == []
    # tighten the limit below the kernel's effective K: the same trace is
    # now flagged, proving the grid axis is counted
    assert OverflowPass(k_limit=128).run(jaxpr) != []


# ---------------------------------------------------------------------------
# OverflowPass: fp8 digit bound
# ---------------------------------------------------------------------------

def _fp8_dot_jaxpr(k):
    a = jax.ShapeDtypeStruct((2, k), jnp.float8_e4m3fn)
    b = jax.ShapeDtypeStruct((k, 3), jnp.float8_e4m3fn)
    return jax.make_jaxpr(
        lambda x, y: jax.lax.dot_general(
            x, y, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    )(a, b)


def test_overflow_fp8_cross_term_bound():
    """The fp8 rule admits concatenated-digit (Karatsuba cross-term) dots up
    to 2*FP8_K_CHUNK_LIMIT and flags one element more."""
    from repro.kernels.fp8_mod_gemm import FP8_K_CHUNK_LIMIT

    assert OverflowPass().run(_fp8_dot_jaxpr(2 * FP8_K_CHUNK_LIMIT)) == []
    findings = OverflowPass().run(_fp8_dot_jaxpr(2 * FP8_K_CHUNK_LIMIT + 1))
    assert len(findings) == 1
    assert "FP8_K_CHUNK_LIMIT" in findings[0].message


def test_overflow_fp8_kernel_launch_at_limit(rng):
    """The real fp8 pallas kernel at its exact chunk limit certifies clean;
    an artificially tighter limit flags the very same trace."""
    from repro.kernels.fp8_mod_gemm import FP8_K_CHUNK_LIMIT, fp8_mod_gemm_batched

    ctx = make_crt_context(4)
    k = FP8_K_CHUNK_LIMIT
    a = jax.ShapeDtypeStruct((len(ctx.moduli), 8, k), jnp.int8)
    b = jax.ShapeDtypeStruct((len(ctx.moduli), k, 8), jnp.int8)
    jaxpr = jax.make_jaxpr(
        lambda x, y: fp8_mod_gemm_batched(x, y, moduli=ctx.moduli, interpret=True)
    )(a, b)
    assert OverflowPass().run(jaxpr) == []
    assert OverflowPass(fp8_limit=FP8_K_CHUNK_LIMIT // 8).run(jaxpr) != []


# ---------------------------------------------------------------------------
# OverflowPass: f64 provable-bound rule (CRT partial dots)
# ---------------------------------------------------------------------------

def _const_dot_jaxpr(scale):
    table = np.full((4, 3), scale)

    def f(x):
        return jnp.dot(x.astype(jnp.float64), jnp.asarray(table))

    return jax.make_jaxpr(f)(jax.ShapeDtypeStruct((2, 4), jnp.int8))


def test_overflow_f64_const_dot_within_window():
    # 127 * 2^40 * 4 ~ 5.6e14 < 2^53: exact, certifies
    assert OverflowPass().run(_const_dot_jaxpr(2.0**40)) == []


def test_overflow_f64_const_dot_beyond_window_flagged():
    # 127 * 2^48 * 4 ~ 1.4e17 > 2^53: the partial-combine would round
    findings = OverflowPass().run(_const_dot_jaxpr(2.0**48))
    assert len(findings) == 1
    assert "2^53" in findings[0].message


# ---------------------------------------------------------------------------
# CollectiveSafetyPass
# ---------------------------------------------------------------------------

def _psum_jaxpr(dtype):
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((1,), ("r",))

    def f(x):
        return shard_map(
            lambda v: jax.lax.psum(v, "r"),
            mesh=mesh, in_specs=P("r"), out_specs=P(),
        )(x)

    return jax.make_jaxpr(f)(jax.ShapeDtypeStruct((4, 4), dtype))


def test_collective_safety_f64_psum_clean():
    jaxpr = _psum_jaxpr(jnp.float64)
    assert CollectiveSafetyPass().run(jaxpr) == []
    # inside shard_map the collective appears as psum2 or, with check_vma
    # on (jax 0.9), psum_invariant
    colls = collect_collectives(jaxpr)
    assert any(name in ("psum", "psum2", "psum_invariant") for name, _ in colls)


def test_collective_safety_int8_psum_flagged():
    findings = CollectiveSafetyPass().run(_psum_jaxpr(jnp.int8))
    assert findings, "int8 crossing the mesh must be a finding"
    for f in findings:
        assert f.pass_name == "collective-safety"
        assert "int8" in f.message


# ---------------------------------------------------------------------------
# LaunchCountPass
# ---------------------------------------------------------------------------

def test_launch_count_zero_for_pure_xla():
    a = jnp.zeros((4, 4))
    assert certify_launch_count(0, jnp.matmul, a, a) == []
    findings = certify_launch_count(3, jnp.matmul, a, a)
    assert len(findings) == 1
    assert "0 pallas_call" in findings[0].message
    assert "predicts 3" in findings[0].message


def test_launch_count_against_real_kernel(rng):
    from repro.core.executor import execute_plan
    from repro.kernels import KernelBackend

    pol = GemmPolicy(backend="ozaki2_f32", n_moduli=4, execution="kernel",
                     interpret=True)
    plan = pol.plan_for(8, 64, 8)
    a = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
    want = expected_launch_count(KernelBackend(interpret=True), plan, (8, 64, 8))
    run = lambda x, y: execute_plan(plan, x, y, KernelBackend(interpret=True))
    assert certify_launch_count(want, run, a, b) == []
    assert certify_launch_count(want + 1, run, a, b) != []
    assert count_pallas_calls(run, a, b) == want


def test_expected_launch_count_zero_for_reference():
    from repro.core.executor import ReferenceBackend

    pol = GemmPolicy(backend="ozaki2_f32", n_moduli=4, execution="reference")
    plan = pol.plan_for(8, 64, 8)
    assert expected_launch_count(ReferenceBackend(), plan, (8, 64, 8)) == 0


# ---------------------------------------------------------------------------
# ScanIndexWidthPass
# ---------------------------------------------------------------------------

def _scan_index_jaxpr(index_dtype):
    x = jnp.zeros((8, 4))

    def f():
        def body(carry, i):
            row = jax.lax.dynamic_slice_in_dim(x, i, 1, axis=0)
            return carry + row.sum(), None

        return jax.lax.scan(body, 0.0, jnp.arange(8, dtype=index_dtype))[0]

    return jax.make_jaxpr(f)()


def test_scan_index_width_int32_clean():
    assert ScanIndexWidthPass().run(_scan_index_jaxpr(jnp.int32)) == []


def test_scan_index_width_int64_flagged():
    findings = ScanIndexWidthPass().run(_scan_index_jaxpr(jnp.int64))
    assert findings, "s64 scan-body index must be a finding"
    f = findings[0]
    assert f.pass_name == "scan-index-width"
    assert f.primitive == "dynamic_slice"
    assert "scan" in f.path


def test_scan_index_width_outside_scan_not_flagged():
    """s64 dynamic_slice OUTSIDE a scan body is fine (no carry involved)."""
    x = jnp.zeros((8, 4))
    jaxpr = jax.make_jaxpr(
        lambda i: jax.lax.dynamic_slice_in_dim(x, i, 1, axis=0)
    )(jnp.int64(3))
    assert ScanIndexWidthPass().run(jaxpr) == []


# ---------------------------------------------------------------------------
# certify_partial_split
# ---------------------------------------------------------------------------

def test_partial_split_tables_certify_for_all_sizes():
    for n in (2, 5, 14, 20):
        ctx = make_crt_context(n)
        assert certify_partial_split(ctx.moduli) == []


def test_partial_split_rejects_bad_tables():
    moduli = make_crt_context(3).moduli
    msgs = [f.message for f in certify_partial_split(
        moduli, u=np.array([[-1.0]]), part_bits=8)]
    assert any("negative" in m for m in msgs)
    msgs = [f.message for f in certify_partial_split(
        moduli, u=np.array([[300.0]]), part_bits=8)]
    assert any("part_bits" in m for m in msgs)
    msgs = [f.message for f in certify_partial_split(
        moduli, u=np.array([[2.0**55]]), part_bits=60)]
    assert any("2^31" in m for m in msgs)


# ---------------------------------------------------------------------------
# backend.analyze hook + run_passes
# ---------------------------------------------------------------------------

def test_backend_analyze_hook_matches_passes_for_backend():
    from repro.core.executor import ReferenceBackend

    pol = GemmPolicy(backend="ozaki2_f32", n_moduli=4, execution="reference")
    plan = pol.plan_for(8, 64, 8)
    backend = ReferenceBackend()
    suite = backend.analyze(plan, (8, 64, 8))
    kinds = [type(p).__name__ for p in suite]
    assert kinds == [
        "OverflowPass", "CollectiveSafetyPass", "ScanIndexWidthPass",
        "LaunchCountPass",
    ]
    # without a shape there is no launch expectation to pin
    assert [type(p).__name__ for p in backend.analyze(plan)] == kinds[:-1]

    a = jnp.zeros((8, 64), jnp.float32)
    b = jnp.zeros((64, 8), jnp.float32)
    from repro.core.executor import execute_plan

    jaxpr = jax.make_jaxpr(lambda x, y: execute_plan(plan, x, y, backend))(a, b)
    assert run_passes(suite, jaxpr) == []


# ---------------------------------------------------------------------------
# property tests: the chunk loop always satisfies the bound the pass proves
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st

    SET = settings(max_examples=20, deadline=None)
    HAVE_HYPOTHESIS = True
except ImportError:  # optional dependency; CI installs it
    HAVE_HYPOTHESIS = False


def _residue_stack(moduli):
    """jnp reference mod-GEMM stack: (N,m,k)x(N,k,n) int8 -> (N,m,n) int8
    canonical symmetric residues (exact as long as k <= K_CHUNK_LIMIT)."""
    q = jnp.asarray(moduli, jnp.int32).reshape(-1, 1, 1)

    def stack(a, b):
        p = jax.lax.dot_general(
            a, b, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.int32,
        )
        r = jnp.remainder(p, q)
        return jnp.where(r > (q - 1) // 2, r - q, r).astype(jnp.int8)

    return stack


if HAVE_HYPOTHESIS:

    @given(st.integers(min_value=1, max_value=256),
           st.integers(min_value=8, max_value=64))
    @SET
    def test_chunked_residue_matmul_always_certifies(k, chunk_limit):
        """For ANY k and chunk limit, the shared K-chunk loop's trace
        certifies under OverflowPass(k_limit=chunk_limit): every engine dot
        it emits contracts at most chunk_limit elements.  The un-chunked
        stack is the control: flagged exactly when k exceeds the limit."""
        from repro.core.executor import chunked_residue_matmul

        ctx = make_crt_context(3)
        stack = _residue_stack(ctx.moduli)
        a = jax.ShapeDtypeStruct((3, 2, k), jnp.int8)
        b = jax.ShapeDtypeStruct((3, k, 2), jnp.int8)
        chunked = jax.make_jaxpr(
            lambda x, y: chunked_residue_matmul(
                stack, x, y, ctx, chunk_limit=chunk_limit
            )
        )(a, b)
        assert OverflowPass(k_limit=chunk_limit).run(chunked) == []
        direct = jax.make_jaxpr(stack)(a, b)
        flagged = OverflowPass(k_limit=chunk_limit).run(direct) != []
        assert flagged == (k > chunk_limit)

    @given(st.integers(min_value=1, max_value=2 * K_CHUNK_LIMIT))
    @SET
    def test_int8_dot_certification_is_exactly_the_limit(k):
        flagged = OverflowPass().run(_int8_dot_jaxpr(k)) != []
        assert flagged == (k > K_CHUNK_LIMIT)

    @given(st.integers(min_value=1, max_value=512),
           st.integers(min_value=8, max_value=128))
    @SET
    def test_fp8_dot_certification_is_twice_the_limit(k, fp8_limit):
        """The fp8 rule is parametric in the limit and always admits exactly
        2*limit (the concatenated Karatsuba cross-term width)."""
        flagged = OverflowPass(fp8_limit=fp8_limit).run(_fp8_dot_jaxpr(k)) != []
        assert flagged == (k > 2 * fp8_limit)

else:  # pragma: no cover - surfaced as an explicit skip, not silence

    @pytest.mark.skip(reason="optional dependency: hypothesis not installed")
    def test_analysis_property_suite():
        pass


# ---------------------------------------------------------------------------
# source lint
# ---------------------------------------------------------------------------

def _fake_repo(tmp_path, *, skip_execution=None, break_cli=None):
    """A minimal repo satisfying the policy-surface lint, with optional
    deliberate defects."""
    import dataclasses as dc

    fields = " ".join(f.name for f in dc.fields(GemmPolicy))
    execs = [e for e in EXECUTIONS if e != skip_execution]
    (tmp_path / "README.md").write_text(
        " ".join(f"`{e}`" for e in execs) + "\n" + fields + "\n"
    )
    cli_body = (
        "import argparse\n"
        "p = argparse.ArgumentParser()\n"
        f"p.add_argument(\"--execution\", choices={list(EXECUTIONS)!r})\n"
        "p.add_argument(\"--rtol\", type=float, default=None)\n"
    )
    broken_body = (
        "import argparse\n"
        "p = argparse.ArgumentParser()\n"
        f"p.add_argument(\"--execution\", choices={list(EXECUTIONS[:-1])!r})\n"
        "p.add_argument(\"--rtol\", type=float, default=None)\n"
    )
    from repro.analysis.lint import EXECUTION_CLIS

    for rel in EXECUTION_CLIS:
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(broken_body if rel == break_cli else cli_body)
    return tmp_path


def test_lint_clean_on_synced_repo(tmp_path):
    assert lint_policy_surface(_fake_repo(tmp_path)) == []


def test_lint_flags_undocumented_execution(tmp_path):
    findings = lint_policy_surface(_fake_repo(tmp_path, skip_execution="fused"))
    assert len(findings) == 1
    assert "`fused`" in findings[0].message
    assert "README" in findings[0].message


def test_lint_flags_out_of_sync_cli(tmp_path):
    broken = "src/repro/launch/train.py"
    findings = lint_policy_surface(_fake_repo(tmp_path, break_cli=broken))
    assert len(findings) == 1
    assert broken in findings[0].message
    assert "missing" in findings[0].message


def test_lint_flags_missing_rtol_flag(tmp_path):
    repo = _fake_repo(tmp_path)
    target = repo / "src/repro/launch/serve.py"
    target.write_text(
        "\n".join(
            line for line in target.read_text().splitlines()
            if "--rtol" not in line
        )
        + "\n"
    )
    findings = lint_policy_surface(repo)
    assert len(findings) == 1
    assert "--rtol" in findings[0].message


def test_lint_flags_missing_cli(tmp_path):
    repo = _fake_repo(tmp_path)
    (repo / "src/repro/launch/serve.py").unlink()
    findings = lint_policy_surface(repo)
    assert len(findings) == 1
    assert "not found" in findings[0].message


def test_execution_choices_none_without_flag(tmp_path):
    p = tmp_path / "noflag.py"
    p.write_text("import argparse\np = argparse.ArgumentParser()\n")
    assert execution_choices(p) is None


def test_real_repo_lints_clean():
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    assert lint_policy_surface(repo) == []


# ---------------------------------------------------------------------------
# walker + CLI plumbing
# ---------------------------------------------------------------------------

def test_walker_counts_nested_primitives():
    def f(x):
        def body(c, _):
            return c * 2.0, None

        y, _ = jax.lax.scan(body, x, None, length=3)
        return jax.jit(jnp.sin)(y)

    jaxpr = jax.make_jaxpr(f)(1.0)
    open_jaxpr, consts = unwrap(jaxpr)
    assert count_primitive(open_jaxpr, "scan") == 1
    prims = {eqn.primitive.name for eqn, _ in iter_eqns(jaxpr)}
    assert "sin" in prims, "iter_eqns must descend into pjit bodies"
    in_scan = [ctx.in_scan_body for eqn, ctx in iter_eqns(jaxpr)
               if eqn.primitive.name == "mul"]
    assert in_scan == [True]


def test_cli_smoke_row_exits_clean(capsys):
    from repro.analysis.__main__ import main

    rc = main([
        "--executions", "reference", "--dtypes", "float32",
        "--modes", "fast", "--skip-model",
    ])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "certified clean" in out


def test_finding_str_static():
    f = Finding("overflow", "boom")
    assert str(f) == "[overflow] <static>: boom"
