"""Compile the Pallas kernels and the emulated GEMM programs for a TPU v5e.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: s64 block indices, 1-D blocks that do not match the TPU
layout, VMEM overruns, f64 bit tricks the TPU's x64 rewriter cannot lower,
c128 program operands.  These tests compile for a described `v5e:2x2`
topology (no chip attached; nothing runs) at 4096 widths with the default
blocks (the complex residue product also at the benchmark's 8192 and 1024,
each at its default tile), and check that each program holds a compiled
Mosaic kernel (`tpu_custom_call`), i.e. that nothing fell back to
interpret mode.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU compiler library.

The cgemm program is also checked for the names a profile shows: each
Mosaic kernel by its `pallas_call` name, each op by the `ozaki2.<stage>`
scope of the executor's stage that made it.  The scopes are metadata only;
a CPU case pins the output bits the program gave before it had them.
"""
import functools
import hashlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import linalg
from repro.core.executor import STAGES
from repro.core.moduli import make_crt_context
from repro.core.plan import default_n_moduli, n_limbs_for_ctx
from repro.core.policy import GemmPolicy
from repro.kernels import (
    crt_garner,
    int8_mod_gemm_batched,
    karatsuba_mod_gemm_batched,
    residue_cast,
)
from repro.kernels.int8_mod_gemm import fused_mod_gemm
from repro.kernels.karatsuba_fused import fused_karatsuba_mod_gemm

W = 4096  # matrix width of every compile
N_REAL = default_n_moduli(jnp.float32, "fast")
N_COMPLEX = default_n_moduli(jnp.complex64, "fast")


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compilation
    cache off (a compile for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, sh):
    """(fn, arg shapes) of one kernel at W widths with its default blocks."""
    ctx_r = make_crt_context(N_REAL)
    ctx_c = make_crt_context(N_COMPLEX)
    f32 = lambda *s: _spec(sh, s, jnp.float32)  # noqa: E731
    i8 = lambda *s: _spec(sh, s, jnp.int8)  # noqa: E731
    i32 = lambda *s: _spec(sh, s, jnp.int32)  # noqa: E731
    if name.startswith("int8_mod_gemm_batched"):
        # ragged: mamba2-130m's in_proj width, whose K block must stay a
        # multiple of 128 lanes
        k = 3360 if name.endswith("ragged_k") else W
        fn = functools.partial(
            int8_mod_gemm_batched, moduli=ctx_r.moduli, interpret=False
        )
        return fn, (i8(N_REAL, W, k), i8(N_REAL, k, W))
    if name.startswith("karatsuba_mod_gemm_batched"):
        # the cells' widths beside W, each at its default tile
        size = int(name.split("_")[-1]) if name[-1].isdigit() else W
        fn = functools.partial(
            karatsuba_mod_gemm_batched, moduli=ctx_c.moduli, interpret=False
        )
        return fn, (i8(N_COMPLEX, size, size),) * 4
    if name == "fused_mod_gemm":
        fn = lambda a, b, em, en: fused_mod_gemm(  # noqa: E731
            a, b, em, en, ctx_r, n_limbs=n_limbs_for_ctx(ctx_r),
            interpret=False,
        )
        return fn, (f32(W, W), f32(W, W), i32(W), i32(W))
    if name == "fused_karatsuba_mod_gemm":
        fn = lambda ar, ai, br, bi, em, en: fused_karatsuba_mod_gemm(  # noqa: E731
            ar, ai, br, bi, em, en, ctx_c, n_limbs=n_limbs_for_ctx(ctx_c),
            interpret=False,
        )
        return fn, (f32(W, W),) * 4 + (i32(W), i32(W))
    if name.startswith("residue_cast"):
        axis = int(name[-1])
        fn = functools.partial(
            residue_cast, moduli=ctx_r.moduli, n_limbs=n_limbs_for_ctx(ctx_r),
            scale_axis=axis, interpret=False,
        )
        return fn, (f32(W, W), f32(W), f32(W))
    if name.startswith("crt_garner"):
        out_dd = name.endswith("dd")
        fn = lambda e, em, en: crt_garner(  # noqa: E731
            e, em, en, ctx_r, out_dd=out_dd, interpret=False
        )
        return fn, (i8(N_REAL, W, W), i32(W), i32(W))
    raise ValueError(name)


@pytest.mark.parametrize(
    "name",
    [
        "int8_mod_gemm_batched",
        "int8_mod_gemm_batched_ragged_k",
        "karatsuba_mod_gemm_batched",
        "karatsuba_mod_gemm_batched_8192",
        "karatsuba_mod_gemm_batched_1024",
        "fused_mod_gemm",
        "fused_karatsuba_mod_gemm",
        "residue_cast_axis0",
        "residue_cast_axis1",
        "crt_garner_f32",
        "crt_garner_dd",
    ],
)
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_case(name, one_chip)
    assert "tpu_custom_call" in _compile_text(fn, *shapes)


@pytest.mark.parametrize("routine", ["cgemm", "zgemm"])
def test_linalg_program_compiles_for_v5e(one_chip, routine):
    """The whole jitted program, scaling and exponent arithmetic included.
    zgemm compiles in its planar form: a TPU program cannot take a c128."""
    pol = GemmPolicy(execution="kernel", interpret=False)
    if routine == "cgemm":
        x = _spec(one_chip, (W, W), jnp.complex64)
        fn = functools.partial(linalg.cgemm, policy=pol)
        shapes = (x, x)
    else:
        x = _spec(one_chip, (W, W), jnp.float64)
        fn = functools.partial(linalg.zgemm_planar, policy=pol)
        shapes = (x,) * 4
    assert "tpu_custom_call" in _compile_text(fn, *shapes)


#: the Mosaic kernels of jitted `linalg.cgemm` on the kernel path (fast,
#: Karatsuba, one n-block), by `pallas_call` name: both operands' casts,
#: the product and the Garner reconstruction
CGEMM_KERNELS = {"residue_cast": 2, "karatsuba_mod_gemm": 1, "crt_garner": 1}
#: opcodes that compute nothing of their own
NO_WORK = {"parameter", "constant", "bitcast", "tuple", "get-tuple-element"}
_LINE = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+) = .*? ([\w\-]+)\((.*?)\)")


def _compiler_made(line: str, params: dict) -> bool:
    """Ops the TPU compiler makes with no source in the program, so no
    scope: the float32 halves of a complex64 argument (`X64SplitLow` /
    `X64SplitHigh` of the parameter, named after the argument), and the
    asynchronous copy of a constant into a kernel's memory space."""
    m = _LINE.match(line)
    op_name = re.search(r'op_name="([^"]*)"', line)
    if 'custom_call_target="X64Split' in line:
        return m.group(3) in params and op_name and op_name.group(1) == params[m.group(3)]
    return m.group(2) in ("copy-start", "copy-done") and op_name is None


def test_cgemm_program_names_its_kernels_and_stages(one_chip):
    """Jitted `linalg.cgemm` compiled for the v5e at 1024: every Mosaic
    kernel is found by its `pallas_call` name (instruction name and
    `op_name`), and every op that computes carries an `ozaki2.<stage>`
    scope, but the ones the compiler makes with no source in a stage."""
    pol = GemmPolicy(execution="kernel", interpret=False)
    x = _spec(one_chip, (1024, 1024), jnp.complex64)
    text = _compile_text(functools.partial(linalg.cgemm, policy=pol), x, x)
    lines = text[text.index("\nENTRY "):].splitlines()[1:]
    entry = [line for line in lines[: lines.index("}")] if _LINE.match(line)]
    params = {}
    for line in entry:
        name, opcode, _ = _LINE.match(line).groups()
        if opcode == "parameter":
            params[name] = re.search(r'op_name="([^"]*)"', line).group(1)
    kernels, unscoped = {}, []
    for line in entry:
        name, opcode, _ = _LINE.match(line).groups()
        op_name = re.search(r'op_name="([^"]*)"', line)
        op_name = op_name.group(1) if op_name else ""
        if 'custom_call_target="tpu_custom_call"' in line:
            kernel = re.search(r"/(\w+)/pallas_call$", op_name).group(1)
            assert re.fullmatch(rf"%{kernel}(\.\d+)?", name), (name, op_name)
            kernels[kernel] = kernels.get(kernel, 0) + 1
        scopes = re.findall(r"ozaki2\.(\w+)", op_name)
        assert set(scopes) <= set(STAGES), op_name
        if opcode not in NO_WORK and not scopes:
            unscoped.append(line)
    assert kernels == CGEMM_KERNELS
    assert unscoped and all(_compiler_made(line, params) for line in unscoped), [
        line[:200] for line in unscoped if not _compiler_made(line, params)]


#: sha256 of the complex64 output bytes of `linalg.cgemm` on the CPU for the
#: operands of `_parity_operands`, as the program gave them before its
#: stages were scoped; all three executions agree to the bit
CGEMM_64x64x256_SHA256 = (
    "fd83c0b6a38058eb89037ce6ee3e4c23e055595749c7eeb35f9fd068a941ed97"
)


def _parity_operands():
    import numpy as np

    rng = np.random.default_rng(20261018)

    def draw(shape):
        return (rng.standard_normal(shape) * np.exp2(rng.integers(-8, 8, shape))
                + 1j * rng.standard_normal(shape)).astype(np.complex64)

    return draw((64, 256)), draw((256, 64))


@pytest.mark.parametrize("execution", ["kernel", "fused", "reference"])
def test_stage_scopes_change_no_output_bit(execution):
    """m = n = 64, k = 256 on the CPU (Pallas in interpret mode): the
    output's bits are the ones pinned before the scopes existed."""
    import numpy as np

    a, b = _parity_operands()
    y = np.asarray(linalg.cgemm(a, b, policy=GemmPolicy(execution=execution)))
    assert y.dtype == np.complex64 and y.shape == (64, 64)
    assert hashlib.sha256(y.tobytes()).hexdigest() == CGEMM_64x64x256_SHA256
