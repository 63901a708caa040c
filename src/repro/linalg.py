"""`repro.linalg` — drop-in matmul routed by the ambient :class:`GemmPolicy`.

This is the library analog of the paper's deployment story: the reference
implementation LD_PRELOAD-interposes cuBLAS so unmodified applications run
the CGEMM/ZGEMM emulation.  Here the interposition point is one function —

    >>> import jax.numpy as jnp
    >>> import repro
    >>> from repro.core import GemmPolicy
    >>> a = jnp.eye(2, dtype=jnp.complex64)
    >>> b = jnp.ones((2, 2), jnp.complex64)
    >>> with repro.use_policy(GemmPolicy(backend="ozaki2_c64", n_moduli=5,
    ...                                  execution="kernel")):
    ...     y = repro.linalg.matmul(a, b)          # batched Pallas path
    >>> (y.dtype.name, bool(jnp.all(y == b)))
    ('complex64', True)

— and everything above it (`repro.models` layers, the serve engine, the
training step) calls `linalg.matmul`, so one `use_policy` scope (or one
`gemm_policy` config field) moves a whole model between the native path,
the jnp reference emulation, the modulus-batched Pallas kernels, the
sharded pipeline and the fp8 engine.

Policy scoping and jit
----------------------

`use_policy` pushes onto a thread-local stack; `current_policy()` reads the
top (default: the native policy).  Policies are frozen/hashable, and
`matmul` captures the ambient policy *at trace time* — inside `jax.jit` the
captured policy is baked into the compiled computation like any other
static.  Enter `use_policy` before tracing (or pass `policy=` explicitly /
pin it in a `ModelConfig`, which resolves the ambient policy once at config
construction); re-entering a different policy after a function was traced
does not retrace it.  `matmul_jit` is provided for eager callers: it jits
per (shapes, policy) with the policy as an explicit static argument.

BLAS-shaped wrappers
--------------------

`sgemm`/`dgemm`/`cgemm`/`zgemm` coerce the operands to the routine's
compute dtype and force the matching ``ozaki2_*`` backend while inheriting
every other knob (mode, execution, formulation, n_block, ...) from the
ambient or given policy — `cgemm(a, b)` is always the emulated complex64
product, whatever the ambient backend field says.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from .core.executor import PreparedOperand
from .core.policy import (
    BACKEND_FOR_DTYPE,
    GemmPolicy,
    NATIVE,
    current_mesh,
    emulated_matmul,
    emulated_matmul_planar,
    policy_matmul,
    prepare_weights,
    resolve_for_operands,
    use_mesh,
)

__all__ = [
    "GemmPolicy",
    "PreparedOperand",
    "cgemm",
    "current_mesh",
    "current_policy",
    "dgemm",
    "matmul",
    "matmul_jit",
    "prepare_weights",
    "sgemm",
    "use_mesh",
    "use_policy",
    "zgemm",
    "zgemm_planar",
]

_STATE = threading.local()


def current_policy() -> GemmPolicy:
    """The innermost active `use_policy` policy (default: native)."""
    stack = getattr(_STATE, "stack", None)
    return stack[-1] if stack else NATIVE


@contextlib.contextmanager
def use_policy(policy: GemmPolicy, *, mesh=None, calibration=None):
    """Scope every `linalg.matmul` (and model/serve/train matmul resolved at
    config construction) in this thread to `policy`.

    Accepts a backend name as shorthand: ``use_policy("ozaki2_c64")``.
    Nestable; the innermost scope wins.  The policy must be hashable (it is
    captured as a jit static).  `mesh` additionally scopes the thread-local
    default mesh (`use_mesh`) a ``GemmPolicy(execution="sharded",
    mesh=None)`` resolves at trace time — one context manager distributes
    every matmul in a model over the mesh.  `calibration` (a
    `repro.tune.Calibration` or cache-file path) additionally scopes the
    thread-local calibration (`repro.use_calibration`), so the 'auto' plan
    selections price against the measured hardware and the kernels launch
    the autotuned block shapes while tracing inside the scope.

    Example — the ambient scope routes matmuls, nesting overrides it::

        >>> import jax.numpy as jnp
        >>> import repro
        >>> from repro.core import GemmPolicy
        >>> repro.current_policy().backend
        'native'
        >>> with repro.use_policy("ozaki2_f64"):         # name shorthand
        ...     outer = repro.current_policy().backend
        ...     with repro.use_policy(GemmPolicy(backend="ozaki2_f32",
        ...                                      execution="fp8")):
        ...         inner = repro.current_policy().execution
        >>> (outer, inner, repro.current_policy().backend)
        ('ozaki2_f64', 'fp8', 'native')
    """
    if isinstance(policy, str):
        policy = GemmPolicy(backend=policy)
    if not isinstance(policy, GemmPolicy):
        raise TypeError(
            f"use_policy expects a GemmPolicy (or backend name); got "
            f"{type(policy).__name__}"
        )
    hash(policy)  # fail fast: the policy rides in jit-static slots
    stack = getattr(_STATE, "stack", None)
    if stack is None:
        stack = _STATE.stack = []
    stack.append(policy)
    try:
        with contextlib.ExitStack() as scopes:
            if mesh is not None:
                scopes.enter_context(use_mesh(mesh))
            if calibration is not None:
                from .tune.cache import use_calibration

                scopes.enter_context(use_calibration(calibration))
            yield policy
    finally:
        stack.pop()


@contextlib.contextmanager
def _no_ambient_policy():
    """Temporarily clear the ambient stack.

    Import-time construction of registry configs must be scope-independent
    (a module first imported inside a `use_policy` scope would otherwise pin
    that scope's policy into its module-level CONFIG forever); the configs
    registry re-pins the ambient policy at lookup instead.
    """
    stack = getattr(_STATE, "stack", None)
    _STATE.stack = []
    try:
        yield
    finally:
        _STATE.stack = stack if stack is not None else []


def matmul(x, w, *, policy: GemmPolicy | None = None, rtol: float | None = None):
    """Drop-in `jnp.matmul(x, w)` under `policy` (default: the ambient
    `use_policy` scope; native when none is active).

    x: (..., m, k); w: (k, n), a batched (..., k, n) array, or a right-side
    `PreparedOperand` (residues cast once — the serving fast path).
    Differentiable through the emulated custom VJP; jit-compatible (the
    policy is trace-time static).

    `rtol` is shorthand for ``dataclasses.replace(policy, rtol=rtol)``: the
    accuracy-adaptive axis (arXiv:2602.02549).  The moduli count — and with
    ``mode="auto"`` the scaling mode — is then resolved per call as the
    cheapest plan whose componentwise error bound provably meets the
    tolerance (see `repro.core.accuracy`).

    Example — an f64-grade product emulated on int8 arithmetic::

        >>> import jax.numpy as jnp
        >>> import repro
        >>> from repro.core import GemmPolicy
        >>> a = jnp.eye(3, dtype=jnp.float64) * 4.0
        >>> b = jnp.full((3, 2), 2.5)
        >>> y = repro.linalg.matmul(
        ...     a, b, policy=GemmPolicy(backend="ozaki2_f64", n_moduli=6))
        >>> bool(jnp.all(y == 10.0))       # exact: power-of-two operands
        True

    Example — ask for a tolerance instead of a moduli count; a looser
    target provably needs fewer moduli (fewer int8 GEMMs)::

        >>> pol = GemmPolicy(backend="ozaki2_f64")
        >>> y6 = repro.linalg.matmul(a, b, policy=pol, rtol=1e-6)
        >>> y14 = repro.linalg.matmul(a, b, policy=pol, rtol=1e-14)
        >>> bool(jnp.allclose(y6, y14))
        True
    """
    policy = current_policy() if policy is None else policy
    if rtol is not None:
        policy = dataclasses.replace(policy, rtol=rtol)
    if isinstance(w, PreparedOperand):
        return policy_matmul(x, w, policy)
    if getattr(x, "ndim", 0) < 2 or getattr(w, "ndim", 0) < 2:
        raise ValueError(
            "linalg.matmul expects matrix operands (ndim >= 2); got shapes "
            f"{getattr(x, 'shape', None)} @ {getattr(w, 'shape', None)}"
        )
    if w.ndim == 2:
        return policy_matmul(x, w, policy)
    # batched weight: the executor's run_plan vectorizes over leading dims
    if policy.backend == "native":
        y = jnp.matmul(x, w)
        return y if policy.out_dtype is None else y.astype(policy.out_dtype)
    if policy.is_adaptive:
        # resolve statically (one plan for every batch element); the 2D
        # fast path above additionally probes the concrete operands
        policy = policy.resolve_adaptive(x.shape[-2], x.shape[-1], w.shape[-1])
    return emulated_matmul(x, w, policy)


@functools.partial(jax.jit, static_argnames=("policy",))
def _matmul_jit(x, w, *, policy):
    return matmul(x, w, policy=policy)


def matmul_jit(x, w, *, policy: GemmPolicy | None = None):
    """`matmul` behind a (shapes, policy)-cached `jax.jit` for eager callers.

    The ambient policy and mesh are resolved *before* jit (`_pin_mesh`).
    """
    policy = current_policy() if policy is None else policy
    return _matmul_jit(x, w, policy=_pin_mesh(policy))


def _pin_mesh(policy: GemmPolicy) -> GemmPolicy:
    """`policy` with the ambient `use_mesh` mesh pinned where its execution
    reads one (sharded; fused runs sharded inside a mesh scope).  A jitted
    entry point resolves it before jit so the context scope can never leak
    stale into the compilation cache (a policy that resolved mesh A at
    first trace must not silently serve mesh B's scope from the cache)."""
    if policy.mesh is None and policy.execution in ("sharded", "fused"):
        return dataclasses.replace(policy, mesh=current_mesh())
    return policy


def _blas_policy(dtype, policy: GemmPolicy | None) -> GemmPolicy:
    """`policy` (default: the ambient one) with the routine's backend."""
    base = current_policy() if policy is None else policy
    return dataclasses.replace(
        base, backend=BACKEND_FOR_DTYPE[jnp.dtype(dtype).name]
    )


def _blas(routine: str, dtype, x, w, policy: GemmPolicy | None):
    dt = jnp.dtype(dtype)
    pol = _blas_policy(dt, policy)
    if isinstance(w, PreparedOperand):
        if jnp.dtype(w.dtype) != dt:
            raise ValueError(
                f"{routine} computes in {dt.name} but the prepared operand "
                f"was cast for {w.dtype}"
            )
        return matmul(x, w, policy=pol)
    return matmul(x.astype(dt), w.astype(dt), policy=pol)


def sgemm(x, w, *, policy: GemmPolicy | None = None):
    """Emulated SGEMM: f32 compute, every other knob (mode, execution,
    n_block, ...) inherited from `policy` / the ambient scope.

    Coerces both operands to float32 and forces ``backend="ozaki2_f32"`` —
    `sgemm(a, b)` is always the emulated f32 product, whatever the ambient
    backend field says.

    >>> import jax.numpy as jnp, repro
    >>> repro.linalg.sgemm(jnp.eye(2), jnp.ones((2, 2))).dtype.name
    'float32'
    """
    return _blas("sgemm", jnp.float32, x, w, policy)


def dgemm(x, w, *, policy: GemmPolicy | None = None):
    """Emulated DGEMM: f64 compute, every other knob from the policy.
    On the kernel/fp8 executions the output is f64-shaped but f32-grade
    (the Pallas cast quantizes through f32).

    >>> import jax.numpy as jnp, repro
    >>> repro.linalg.dgemm(jnp.eye(2), jnp.ones((2, 2))).dtype.name
    'float64'
    """
    return _blas("dgemm", jnp.float64, x, w, policy)


def cgemm(x, w, *, policy: GemmPolicy | None = None):
    """Emulated CGEMM (paper SIII): complex64 compute; the complex product
    strategy is the policy's `formulation` (Fig. 1), default Karatsuba.

    >>> import jax.numpy as jnp, repro
    >>> a = jnp.eye(2) * (1 + 1j)
    >>> repro.linalg.cgemm(a, a).dtype.name
    'complex64'
    """
    return _blas("cgemm", jnp.complex64, x, w, policy)


def zgemm(x, w, *, policy: GemmPolicy | None = None):
    """Emulated ZGEMM (paper SIII): complex128 compute — the headline
    routine on hardware with no native f64 (TPU v5e).

    A TPU program cannot take or return a c128 array, so concrete operands
    are split into planar f64 (re, im) parts on the host, the jitted
    `zgemm_planar` program runs on those, and the c128 result is assembled
    on the host: it comes back as a NumPy array on every backend, the CPU
    included.  Inside a caller's `jit` (traced operands) and for prepared
    weights the product takes the complex route, which only the CPU
    compiles; call `zgemm_planar` there.

    >>> import jax.numpy as jnp, repro
    >>> a = jnp.eye(2, dtype=jnp.complex128) * 2j
    >>> y = repro.linalg.zgemm(a, a)
    >>> (y.dtype.name, complex(y[0, 0]))
    ('complex128', (-4+0j))
    """
    if isinstance(w, PreparedOperand) or any(
        isinstance(v, jax.core.Tracer) for v in (x, w)
    ):
        return _blas("zgemm", jnp.complex128, x, w, policy)
    pol = _pin_mesh(_blas_policy(jnp.complex128, policy))
    x = np.asarray(x, np.complex128)
    w = np.asarray(w, np.complex128)
    if w.ndim == 2 and x.ndim >= 2:
        # probe the c128 operands here: the planar program cannot
        pol = resolve_for_operands(pol, x.reshape(-1, x.shape[-1]), w)
    cr, ci = _zgemm_planar_jit(x.real, x.imag, w.real, w.imag, policy=pol)
    cr, ci = np.asarray(cr), np.asarray(ci)
    out = np.empty(cr.shape, np.result_type(cr.dtype, np.complex64))
    out.real, out.imag = cr, ci
    return out


def zgemm_planar(xr, xi, wr, wi, *, policy: GemmPolicy | None = None):
    """ZGEMM on planar f64 operands: (xr + i xi) @ (wr + i wi) -> (cr, ci).

    The form of `zgemm` that traces into a caller's `jit` on any backend:
    no c128 array enters or leaves the program.  x parts: (..., m, k);
    w parts: (k, n) or batched (..., k, n).  Every knob except the backend
    comes from `policy` / the ambient scope, as for `zgemm`.  Forward only.

    >>> import jax.numpy as jnp, repro
    >>> eye = jnp.eye(2, dtype=jnp.float64)
    >>> cr, ci = repro.linalg.zgemm_planar(0 * eye, 2 * eye, 0 * eye, 2 * eye)
    >>> (cr.dtype.name, float(cr[0, 0]), float(ci[0, 0]))
    ('float64', -4.0, 0.0)
    """
    pol = _blas_policy(jnp.complex128, policy)
    if getattr(xr, "ndim", 0) < 2 or getattr(wr, "ndim", 0) < 2:
        raise ValueError(
            "zgemm_planar expects matrix operands (ndim >= 2); got shapes "
            f"{getattr(xr, 'shape', None)} @ {getattr(wr, 'shape', None)}"
        )
    if pol.is_adaptive:  # static: the planar parts cannot be probed apart
        pol = pol.resolve_adaptive(xr.shape[-2], xr.shape[-1], wr.shape[-1])
    if wr.ndim > 2:
        return emulated_matmul_planar(xr, xi, wr, wi, pol)
    # (..., m, k) @ (k, n): fold the leading dims into rows, as policy_matmul
    lead = xr.shape[:-1]
    cr, ci = emulated_matmul_planar(
        *(v.reshape(-1, v.shape[-1]) for v in (xr, xi)), wr, wi, pol
    )
    return cr.reshape(lead + cr.shape[-1:]), ci.reshape(lead + ci.shape[-1:])


_zgemm_planar_jit = jax.jit(zgemm_planar, static_argnames=("policy",))
