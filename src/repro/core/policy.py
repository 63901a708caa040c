"""GEMM backend policy — the framework-facing integration of the technique.

A :class:`GemmPolicy` is one hashable object answering every static question
about a matmul: *what* to emulate (``backend`` — the compute dtype class),
*how precisely* (``n_moduli``/``mode``/``method``/``out_dtype``), *which
complex strategy* (``formulation``/``n_block``), and — since this layer
became the seam for every execution target — *where* to run it:

    execution="reference"           jnp reference data path (exact f64 host)
    execution="kernel"              modulus-batched Pallas kernels (the TPU
                                    path; 4 launches per GEMM at any N)
    execution="per_modulus_kernel"  pre-batching Pallas path (one launch per
                                    modulus; bitwise parity reference)
    execution="sharded"             the kernel pipeline under `shard_map`
                                    over a mesh: residue planes shard N over
                                    the 'residue' axis (falling back to
                                    'model'), m/n shard like a normal GEMM,
                                    and one psum of the reconstructed output
                                    is the only communication
                                    (`distributed/sharded_gemm.py`)
    execution="fp8"                 the FP8 (e4m3) engine: residue products
                                    as exact base-16 digit GEMMs with
                                    per-plane rescale, bitwise identical to
                                    "kernel" but priced at the e4m3 rate
                                    (`kernels/fp8_mod_gemm.py`,
                                    arXiv:2603.10634)
    execution="fused"               the one-launch megakernel: residue casts
                                    as the kernel prologue, Garner
                                    reconstruction as its epilogue, K-chunk
                                    carries in-kernel — a fast-mode GEMM is
                                    exactly one `pallas_call`, bitwise
                                    identical to "kernel".  With an ambient
                                    `use_mesh` (or pinned ``mesh=``) the
                                    fused worker runs under the sharded
                                    pipeline (m/n sharding; residue-sharded
                                    meshes fall back to the composed worker)

The sharded execution needs a mesh: pin it on the policy (``mesh=``) or
scope a thread-local default with :func:`use_mesh` (also reachable as
``repro.use_mesh`` and via ``repro.use_policy(policy, mesh=...)``).
``shard_axes`` optionally overrides the (residue, m, n) mesh-axis names.

Execution targets plug in as new ``execution`` values resolved by
:meth:`GemmPolicy.execution_backend`; the plan/executor layer
(`core/plan.py` + `core/executor.py`) is backend-agnostic — the fp8 engine
and the fused megakernel are the existence proofs that the protocol
generalizes beyond per-stage int8 kernels.

User code normally does not call this module directly: `repro.linalg.matmul`
is the drop-in entry point, scoped by `repro.use_policy(policy)` — the
analog of the paper's LD_PRELOAD interposition of cuBLAS calls, but
composable, context-scoped and differentiable.  Any dense layer in
`repro.models` routes its matmuls through the same function, so the paper's
emulation is a first-class, config-selectable feature (`gemm_policy` in the
arch configs).

Backends cover both halves of the paper: `ozaki2_f32`/`ozaki2_f64` run the
real SGEMM/DGEMM emulation, `ozaki2_c64`/`ozaki2_c128` the complex
CGEMM/ZGEMM emulation (SIII) with a selectable Fig. 1 `formulation` and
output-column `n_block`.  All four build an `EmulationPlan` and run the
shared executor with the policy's resolved execution backend.

The emulated forward is wrapped in a custom VJP: trunc() has zero gradient,
but the emulation approximates an exact GEMM to (beyond-)float precision, so
the correct cotangents are those of the exact GEMM — themselves computed with
the same emulated backend (keeping the whole training step int8-dominated).
For complex operands the cotangents use the plain (non-conjugating)
transpose, matching JAX's `dot_general` transpose rule, so `jax.grad` of a
real-valued loss through complex emulated matmuls agrees with the native
path.

Weight-stationary callers (serving) may pass a `PreparedOperand` as the
weight: its scaling + residue planes were cast once up front — by the
*selected* execution backend, so prepared serving stays bit-identical to the
unprepared run on the kernel path too — and the per-call work drops to the
activation side only (see `prepare_weights`).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from .executor import (
    PreparedOperand,
    REFERENCE,
    gemm_prepared,
    run_plan,
    run_plan_planar,
)
from .plan import default_n_moduli, make_plan

Backend = Literal[
    "native", "ozaki2_f32", "ozaki2_f64", "ozaki2_c64", "ozaki2_c128"
]

Execution = Literal[
    "reference", "kernel", "per_modulus_kernel", "sharded", "fp8", "fused"
]

EXECUTIONS = (
    "reference", "kernel", "per_modulus_kernel", "sharded", "fp8", "fused"
)


# ------------------------------------------------- thread-local default mesh

_MESH_STATE = threading.local()


def current_mesh():
    """The innermost `use_mesh` mesh (None outside any scope) — the default
    a ``GemmPolicy(execution="sharded", mesh=None)`` resolves at trace time."""
    stack = getattr(_MESH_STATE, "stack", None)
    return stack[-1] if stack else None


@contextlib.contextmanager
def use_mesh(mesh):
    """Scope the thread-local default mesh for sharded-execution policies.

    Nestable; the innermost scope wins.  `repro.use_policy(policy, mesh=...)`
    enters this scope alongside the policy scope, so one context manager
    distributes every matmul in a model.

    Example — a mesh-less sharded policy resolves the ambient mesh::

        >>> import jax, repro
        >>> from repro.core import GemmPolicy
        >>> mesh = jax.make_mesh((1,), ("residue",))
        >>> pol = GemmPolicy(backend="ozaki2_f32", execution="sharded")
        >>> with repro.use_mesh(mesh):
        ...     resolved = pol.resolved_mesh()
        >>> resolved is mesh
        True
    """
    from jax.sharding import Mesh

    if not isinstance(mesh, Mesh):
        raise TypeError(f"use_mesh expects a jax.sharding.Mesh; got {type(mesh).__name__}")
    stack = getattr(_MESH_STATE, "stack", None)
    if stack is None:
        stack = _MESH_STATE.stack = []
    stack.append(mesh)
    try:
        yield mesh
    finally:
        stack.pop()

_COMPUTE_DTYPES = {
    "native": None,
    "ozaki2_f32": jnp.float32,
    "ozaki2_f64": jnp.float64,
    "ozaki2_c64": jnp.complex64,
    "ozaki2_c128": jnp.complex128,
}

# the ozaki2_* backend matching each compute dtype (used by the linalg
# BLAS-shaped wrappers and the legacy entry-point shims)
BACKEND_FOR_DTYPE = {
    "float32": "ozaki2_f32",
    "float64": "ozaki2_f64",
    "complex64": "ozaki2_c64",
    "complex128": "ozaki2_c128",
}


@dataclasses.dataclass(frozen=True)
class GemmPolicy:
    """Static (hashable) matmul policy threaded through the model configs.

    One policy object answers every static question about a matmul.  The
    fields, axis by axis:

    ``backend``
        *What to emulate* — the compute dtype class: ``"native"`` (plain
        ``jnp.matmul``, no emulation) or ``"ozaki2_f32"`` / ``"ozaki2_f64"``
        / ``"ozaki2_c64"`` / ``"ozaki2_c128"`` (SGEMM/DGEMM/CGEMM/ZGEMM
        emulation; operands are coerced to that dtype).
    ``n_moduli``
        Number of CRT moduli N (None: the paper's per-(dtype, mode) default,
        `plan.DEFAULT_MODULI`).  More moduli = more accuracy, more int8/fp8
        work.
    ``mode``
        Scaling mode: ``"fast"`` (Cauchy-Schwarz bound, eqs. 11-12),
        ``"accu"`` (auxiliary 7-bit product bound, eqs. 13-14 — tighter, one
        extra product), or ``"auto"`` (requires ``rtol``): resolve the
        cheapest (mode, n_moduli) pair that provably meets the tolerance,
        priced by the calibrated perfmodel (`perfmodel.select_mode`).
    ``rtol``
        Accuracy-adaptive target (arXiv:2602.02549): the componentwise
        tolerance ``max_ij |C - C_emul|_ij / (k amax_i bmax_j)`` the
        emulation must provably meet.  With ``n_moduli=None`` the moduli
        count is resolved per call via `core.accuracy.min_moduli_for`
        (a cheap dynamic-range probe of concrete operands tightens the
        bound; under jit the static worst case applies — both provably meet
        the tolerance).  With an explicit ``n_moduli`` the pin is kept and
        validated against the bound instead.  None (default): nothing
        adaptive — behavior is bitwise identical to a policy without this
        field.  The native backend ignores ``rtol`` (no emulation step to
        adapt).
    ``method``
        CRT reconstruction: ``"paper"`` (eq. (5) split), ``"dd"``
        (double-double), ``"garner"`` (mixed-radix, the TPU-native kernel),
        or ``"auto"`` — paper on the reference execution, garner on every
        kernel execution (the only reconstruction the kernels implement; no
        f64 on the VPU).
    ``formulation``
        Complex-product strategy (paper Fig. 1): ``"karatsuba"`` (eq. 10),
        ``"block_a"`` / ``"block_b"`` (the eqs. 7/8 embeddings), or
        ``"auto"`` (SIII-C perfmodel per shape, priced at the executing
        backend's launch capabilities and engine).  Ignored for real
        backends.
    ``n_block``
        Output-column blocking (paper SIII-A): an int, None (unblocked), or
        ``"auto"`` (the paper's 8192 columns, balanced).
    ``execution``
        *Where to run it* — the residue backend: ``"reference"`` |
        ``"kernel"`` | ``"per_modulus_kernel"`` | ``"sharded"`` | ``"fp8"``
        | ``"fused"`` (see module docstring; resolved by
        :meth:`execution_backend`).
    ``interpret``
        Forces/forbids Pallas interpret mode for the kernel executions
        (None = auto: interpret off-TPU).
    ``out_dtype``
        Result dtype name (None: the compute dtype) — e.g. f64-shaped
        output from f32 operands.
    ``mesh`` / ``shard_axes``
        Sharded execution only: the mesh to distribute over (None: the
        thread-local `use_mesh` default, resolved at trace time) and an
        optional override of the resolved (residue, m, n) mesh-axis names.
        Both hashable, so sharded policies remain valid jit statics.
    ``calibration``
        Optional path of a `repro.tune` calibration cache to pin: every
        'auto' decision of this policy then prices against that file's
        *measured* `HW`, and its kernel launches use that file's autotuned
        block shapes — regardless of the ambient `use_calibration` scope.
        None (default): the ambient scope decides (presets + static default
        blocks when no scope is active).  A missing/stale/corrupt pinned
        file warns once and degrades to the presets; pinning never changes
        numerics, only the plan pricing and tile shapes.

    Example::

        >>> from repro.core import GemmPolicy
        >>> pol = GemmPolicy(backend="ozaki2_c128", mode="accu",
        ...                  execution="fp8", n_block=8192)
        >>> (pol.compute_dtype.__name__, pol.is_complex, pol.resolved_method)
        ('complex128', True, 'garner')
        >>> pol.plan_for(256, 256, 256).n_moduli     # paper default for accu
        14
    """

    backend: Backend = "native"
    n_moduli: int | None = None
    mode: str = "fast"            # 'fast' | 'accu' | 'auto' (needs rtol)
    method: str = "auto"          # CRT reconstruction path (or 'auto')
    formulation: str = "karatsuba"  # complex Fig. 1 strategy (or 'auto')
    n_block: int | str | None = None  # output-column blocking (or 'auto')
    execution: Execution = "reference"
    interpret: bool | None = None  # Pallas interpret override (kernel paths)
    out_dtype: str | None = None  # result dtype name (None: compute dtype)
    mesh: object | None = None    # sharded execution: jax.sharding.Mesh
    shard_axes: tuple | None = None  # sharded: (residue, m, n) name override
    calibration: str | None = None  # repro.tune cache path to pin (or None)
    rtol: float | None = None     # componentwise accuracy target (adaptive)

    def __post_init__(self):
        if self.backend not in _COMPUTE_DTYPES:
            raise ValueError(f"unknown backend {self.backend!r}")
        if self.mode not in ("fast", "accu", "auto"):
            raise ValueError(
                f"unknown mode {self.mode!r}; expected 'fast', 'accu' or 'auto'"
            )
        if self.rtol is not None and not float(self.rtol) > 0.0:
            raise ValueError(f"rtol must be > 0, got {self.rtol!r}")
        if self.mode == "auto" and self.rtol is None:
            raise ValueError(
                "mode='auto' picks the cheapest (mode, n_moduli) pair meeting "
                "an accuracy target — pass GemmPolicy(rtol=...) to declare it"
            )
        if self.execution not in EXECUTIONS:
            raise ValueError(
                f"unknown execution {self.execution!r}; expected one of "
                f"{EXECUTIONS}"
            )
        if self.execution != "reference" and self.method not in ("auto", "garner"):
            raise ValueError(
                f"execution={self.execution!r} reconstructs via the Garner "
                f"kernel only; method={self.method!r} is reference-path only"
            )
        if self.out_dtype is not None:
            # normalize to the dtype's canonical name so the policy hash is
            # stable across jnp.float32 / 'float32' / np.dtype spellings
            object.__setattr__(self, "out_dtype", jnp.dtype(self.out_dtype).name)

    @property
    def compute_dtype(self):
        return _COMPUTE_DTYPES[self.backend]

    @property
    def is_complex(self) -> bool:
        return self.backend in ("ozaki2_c64", "ozaki2_c128")

    @property
    def resolved_method(self) -> str:
        """The CRT reconstruction this policy actually runs."""
        if self.method != "auto":
            return self.method
        return "paper" if self.execution == "reference" else "garner"

    def resolved_mesh(self):
        """The mesh a sharded execution runs on: the pinned field, else the
        thread-local `use_mesh` default (resolved at trace time)."""
        mesh = self.mesh if self.mesh is not None else current_mesh()
        if mesh is None:
            raise ValueError(
                "execution='sharded' needs a mesh: pass GemmPolicy(mesh=...) "
                "or enter repro.use_mesh(mesh) / repro.use_policy(policy, "
                "mesh=mesh) around tracing"
            )
        return mesh

    def resolved_calibration(self):
        """The `repro.tune.Calibration` this policy's decisions read: the
        pinned ``calibration`` file (memoized; warns once and yields None
        when unfit), else the ambient `use_calibration`/`set_calibration`
        one, else None (presets + static default blocks)."""
        from ..tune.cache import current_calibration, load_calibration_cached

        if self.calibration is not None:
            return load_calibration_cached(self.calibration)
        return current_calibration()

    def _calibration_scope(self):
        """Context manager activating the pinned calibration file (a no-op
        without one — the ambient scope then applies as-is).  Entered around
        plan selection AND kernel tracing, so the perfmodel's `default_hw`
        and the kernels' `resolve_blocks` both see the pinned cache."""
        if self.calibration is None:
            return contextlib.nullcontext()
        from ..tune.cache import load_calibration_cached, use_calibration

        cal = load_calibration_cached(self.calibration)
        if cal is None:
            return contextlib.nullcontext()
        return use_calibration(cal)

    def execution_backend(self):
        """Resolve the residue-backend instance for this policy's execution.

        The returned object is hashable (frozen dataclass) so it can ride in
        jit-static slots; `interpret` is resolved here — *outside* any jitted
        function — so an unset value never causes an avoidable retrace.
        """
        if self.execution == "reference":
            return REFERENCE
        # lazy import: core stays importable without pulling the Pallas stack
        from ..kernels.common import interpret_default
        from ..kernels.ops import KernelBackend, PerModulusKernelBackend

        interp = (
            self.interpret if self.interpret is not None else interpret_default()
        )
        if self.execution == "sharded":
            from ..distributed.sharded_gemm import ShardedBackend

            return ShardedBackend(
                KernelBackend(bool(interp)), self.resolved_mesh(),
                self.shard_axes,
            )
        if self.execution == "fp8":
            from .executor import Fp8Backend

            return Fp8Backend(bool(interp))
        if self.execution == "fused":
            from ..kernels.ops import FusedBackend

            be = FusedBackend(bool(interp))
            # optional-mesh: inside a use_mesh scope (or with mesh= pinned)
            # the fused worker runs under the sharded pipeline; without one
            # it is the plain single-device megakernel
            mesh = self.mesh if self.mesh is not None else current_mesh()
            if mesh is not None:
                from ..distributed.sharded_gemm import ShardedBackend

                return ShardedBackend(be, mesh, self.shard_axes)
            return be
        cls = (
            KernelBackend
            if self.execution == "kernel"
            else PerModulusKernelBackend
        )
        return cls(bool(interp))

    @property
    def is_adaptive(self) -> bool:
        """True when (mode, n_moduli) are deferred to per-call resolution —
        ``mode='auto'``, or ``rtol`` with no pinned ``n_moduli`` (see
        :meth:`resolve_adaptive`).  A pinned ``n_moduli`` alongside ``rtol``
        is *not* adaptive: the pin runs as-is and the declared tolerance is
        certified statically by `analysis.AccuracyPass` instead — which also
        means a policy resolve_adaptive returns (concrete mode, concrete
        n_moduli, rtol kept) runs one fixed plan everywhere, including the
        cotangent products whose contraction length differs."""
        return self.backend != "native" and (
            self.mode == "auto" or (self.rtol is not None and self.n_moduli is None)
        )

    def resolve_adaptive(self, m: int, k: int, n: int, *, stats=None):
        """Resolve ``rtol`` / ``mode='auto'`` to a concrete policy.

        Returns ``self`` unchanged when nothing is adaptive (the bitwise
        no-change guarantee for non-adaptive policies).  Otherwise: the
        admissible (mode, n_moduli) pairs come from the arXiv:2602.02549
        bound calculator (`core.accuracy`) — ``n_moduli=None`` resolves via
        `min_moduli_for`, a pinned ``n_moduli`` is validated against
        `rel_bound` — and `perfmodel.select_mode` picks the cheapest pair on
        this machine (the live `repro.tune` calibration when one is active).
        ``stats`` is an optional `core.accuracy.GemmStats` probe of the
        concrete operands that tightens the bound; ``None`` (e.g. under jit,
        or on the prepared/serving path, which must resolve identically at
        prepare and serve time) certifies the static worst case instead.
        The returned policy keeps ``rtol`` so the resolved plan carries its
        accuracy contract for `analysis.AccuracyPass`.
        """
        if not self.is_adaptive:
            return self
        from . import accuracy, perfmodel

        dtype = jnp.dtype(self.compute_dtype).name
        form = self.formulation if self.is_complex else None
        modes = ("fast", "accu") if self.mode == "auto" else (self.mode,)
        cands, reasons = [], []
        for mode in modes:
            if self.n_moduli is not None:
                bound = accuracy.rel_bound(
                    dtype, mode, self.n_moduli, k, formulation=form,
                    stats=stats, out_dtype=self.out_dtype,
                )
                if self.rtol is not None and bound > self.rtol:
                    reasons.append(
                        f"{mode}: bound {bound:g} at the pinned "
                        f"n_moduli={self.n_moduli} exceeds rtol"
                    )
                    continue
                cands.append((mode, self.n_moduli))
            else:
                try:
                    cands.append((mode, accuracy.min_moduli_for(
                        self.rtol, dtype, k=k, mode=mode, formulation=form,
                        stats=stats, out_dtype=self.out_dtype,
                    )))
                except ValueError as e:
                    reasons.append(f"{mode}: {e}")
        if not cands:
            raise ValueError(
                f"no (mode, n_moduli) meets rtol={self.rtol:g} for "
                f"backend={self.backend!r} at k={k}: " + "; ".join(reasons)
            )
        prec = {"float32": "s", "float64": "d",
                "complex64": "c", "complex128": "z"}[dtype]
        with self._calibration_scope():
            mode, n_moduli = perfmodel.select_mode(
                m, n, k, cands, prec=prec,
                engine="fp8" if self.execution == "fp8" else "int8",
            )
        if (mode, n_moduli) == (self.mode, self.n_moduli):
            return self  # already concrete (and re-validated): fixed point
        return dataclasses.replace(self, mode=mode, n_moduli=n_moduli)

    def plan_for(self, m: int, k: int, n: int):
        """The `EmulationPlan` this policy runs for an (m,k)x(k,n) product.

        Selected inside the policy's calibration scope: with a pinned (or
        ambient) `repro.tune` calibration, every `hw=None` perfmodel term
        below — the sharded comm pricing and the formulation/n_block/engine
        'auto' selections in `make_plan` — resolves `perfmodel.default_hw()`
        to the *measured* hardware instead of the TPU v5e preset.  An
        adaptive policy (``rtol`` / ``mode='auto'``) resolves its concrete
        (mode, n_moduli) first — statically here; callers holding concrete
        operands probe them and resolve before reaching this point.
        """
        if self.backend == "native":
            raise ValueError("native policy has no emulation plan")
        if self.is_adaptive:
            resolved = self.resolve_adaptive(m, k, n)
            if resolved is not self:
                return resolved.plan_for(m, k, n)
        # the perfmodel terms behind the 'auto' selections depend on how the
        # executing backend launches — read its declared capabilities so
        # plan_for and gemm_prepared can never disagree
        with self._calibration_scope():
            be = self.execution_backend()
            shape = (m, k, n)
            comm_s = 0.0
            factors = getattr(be, "shard_factors", None)
            if factors is not None:
                # sharded: price the per-shard problem plus the psum term, so
                # the 'auto' selections reflect what each shard actually runs
                from . import perfmodel

                md, nd, r = factors(m, n)
                shape = (m // md, k, n // nd)
                comm_s = perfmodel.sharded_comm_time_s(
                    shape[0], shape[2],
                    self.n_moduli
                    or default_n_moduli(self.compute_dtype, self.mode),
                    r, complex_=self.is_complex,
                )
            return make_plan(
                self.compute_dtype,
                n_moduli=self.n_moduli,
                mode=self.mode,
                method=self.resolved_method,
                formulation=self.formulation if self.is_complex else None,
                out_dtype=self.out_dtype,
                n_block=self.n_block,
                shape=shape,
                fused_karatsuba=getattr(be, "fused_karatsuba", False),
                modulus_batched=getattr(be, "modulus_batched", False),
                megakernel=getattr(be, "megakernel", False),
                comm_s=comm_s,
                engine=getattr(be, "engine", "int8"),
                rtol=self.rtol,
            )


NATIVE = GemmPolicy()


def _real_cast(y: jnp.ndarray, dtype) -> jnp.ndarray:
    """astype that is explicit about dropping an imaginary part."""
    if jnp.issubdtype(y.dtype, jnp.complexfloating) and not jnp.issubdtype(
        jnp.dtype(dtype), jnp.complexfloating
    ):
        y = jnp.real(y)
    return y.astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def emulated_matmul(x: jnp.ndarray, w: jnp.ndarray, policy: GemmPolicy):
    return _emulated_fwd_raw(x, w, policy)


def _emulated_fwd_raw(x, w, policy):
    ct = policy.compute_dtype
    plan = policy.plan_for(x.shape[-2], x.shape[-1], w.shape[-1])
    # trace under the pinned calibration (a no-op without one) so the
    # kernels' `resolve_blocks` launches the policy's tuned tile shapes
    with policy._calibration_scope():
        y = run_plan(
            plan, x.astype(ct), w.astype(ct),
            backend=policy.execution_backend(),
        )
    return _real_cast(y, policy.out_dtype or x.dtype)


def _emulated_fwd(x, w, policy):
    return _emulated_fwd_raw(x, w, policy), (x, w)


def _emulated_bwd(policy, res, g):
    x, w = res
    # dX = G @ W^T, dW = X^T @ G — also emulated (int8-engine dominated).
    # Plain transposes (no conjugation) match JAX's dot_general transpose
    # rule, so complex operands differentiate identically to jnp.matmul.
    dx = _emulated_fwd_raw(g, w.swapaxes(-1, -2), policy)
    dw = _emulated_fwd_raw(x.swapaxes(-1, -2), g, policy)
    return _real_cast(dx, x.dtype), _real_cast(dw, w.dtype)


emulated_matmul.defvjp(_emulated_fwd, _emulated_bwd)


def emulated_matmul_planar(xr, xi, wr, wi, policy: GemmPolicy):
    """The complex emulated product on planar operands -> planar (cr, ci).

    (xr + i xi) @ (wr + i wi) under a complex `policy`, with no complex
    array anywhere in the program: a c128 product on a TPU, which cannot
    hold a c128 array, runs this way.  Forward only (no custom VJP).
    """
    if not policy.is_complex:
        raise ValueError(
            f"planar products need a complex policy backend; got "
            f"{policy.backend!r}"
        )
    plan = policy.plan_for(xr.shape[-2], xr.shape[-1], wr.shape[-1])
    rt = jnp.finfo(policy.compute_dtype).dtype
    with policy._calibration_scope():
        return run_plan_planar(
            plan, *(v.astype(rt) for v in (xr, xi, wr, wi)),
            backend=policy.execution_backend(),
        )


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _prepared_matmul(x: jnp.ndarray, w: PreparedOperand, policy: GemmPolicy):
    """x @ w with the weight prepared up front (inference only)."""
    ct = policy.compute_dtype
    with policy._calibration_scope():
        y = gemm_prepared(
            w,
            x.astype(ct),
            method=policy.resolved_method,
            formulation=policy.formulation,
            out_dtype=policy.out_dtype,
            n_block=policy.n_block,
            backend=policy.execution_backend(),
            mode=policy.mode,
        )
    return _real_cast(y, policy.out_dtype or x.dtype)


def _prepared_fwd(x, w, policy):
    return _prepared_matmul(x, w, policy), None


def _prepared_bwd(policy, res, g):
    # The prepared residues carry only the weight-side scaling, which is the
    # wrong axis for the cotangent products — grads would silently vanish
    # through trunc().  Training must use raw weights.
    raise ValueError(
        "prepared-weight matmuls are inference-only; differentiate through "
        "raw weights (emulated_matmul) instead"
    )


_prepared_matmul.defvjp(_prepared_fwd, _prepared_bwd)


def policy_matmul(x: jnp.ndarray, w, policy: GemmPolicy) -> jnp.ndarray:
    """x: (..., k) @ w: (k, n) under the policy's backend and execution.

    `w` may be a raw array or a right-side `PreparedOperand` (weights cast
    once, amortized across calls — the serving fast path).  This is the
    layer-shaped entry point; the general drop-in (batched `w`, ambient
    policy) is `repro.linalg.matmul`, which routes here.
    """
    if isinstance(w, PreparedOperand):
        if policy.backend == "native":
            raise ValueError(
                "prepared weights require an emulated (ozaki2_*) policy "
                "backend; the native policy runs jnp.matmul on raw weights"
            )
        if w.side != "right":
            raise ValueError("policy_matmul expects a side='right' prepared weight")
        if policy.execution == "sharded" or (
            policy.execution == "fused"
            and (policy.mesh is not None or current_mesh() is not None)
        ):
            raise NotImplementedError(
                "prepared weights are not supported under a sharded "
                "execution yet (the prepared residue planes live unsharded "
                "on one device); serve prepared weights with GemmPolicy("
                "execution='kernel') or execution='fused' outside any mesh "
                "scope, or pass raw weights to shard this matmul"
            )
        k, n = w.operand_shape
        # adaptive policies resolve *statically* on the prepared path — no
        # operand probe, and a canonical pricing shape (m := n) independent
        # of the batch — so prepare_weights and this call agree whenever the
        # policy and weight shape are unchanged; any drift (rtol edited
        # between prepare and serve, a different adaptive pick) is caught by
        # the recorded-plan checks below instead of returning wrong answers
        policy = policy.resolve_adaptive(n, k, n)
        if policy.mode == "accu" and w.raw is None:
            raise ValueError(
                "accu-mode prepared matmuls re-cast from the raw operand "
                "(the accurate exponents couple both operands); re-prepare "
                "with prepare_weights(accu policy) / keep_raw=True"
            )
        if w.mode != policy.mode:
            raise ValueError(
                f"prepared weight was prepared for mode={w.mode!r} but the "
                f"policy resolves to mode={policy.mode!r}"
                + (" (adaptive resolution)" if policy.rtol is not None else "")
                + "; re-prepare with prepare_weights(policy)"
            )
        expect = policy.n_moduli or default_n_moduli(
            policy.compute_dtype, policy.mode
        )
        if w.n_moduli != expect:
            raise ValueError(
                f"prepared weight has n_moduli={w.n_moduli} but the policy "
                f"resolves to {expect}"
                + (" (adaptive resolution)" if policy.rtol is not None else "")
                + "; re-prepare with prepare_weights(policy)"
            )
        if jnp.dtype(w.dtype) != jnp.dtype(policy.compute_dtype):
            raise ValueError(
                f"prepared weight was cast for {w.dtype} but the policy "
                f"computes in {jnp.dtype(policy.compute_dtype).name}; "
                "re-prepare with prepare_weights(policy)"
            )
        n = w.operand_shape[1]
        lead = x.shape[:-1]
        y = _prepared_matmul(x.reshape((-1, x.shape[-1])), w, policy)
        return y.reshape(lead + (n,))
    if policy.backend == "native":
        y = jnp.matmul(x, w)
        return y if policy.out_dtype is None else y.astype(policy.out_dtype)
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))
    # adaptive resolution happens *before* the custom-VJP boundary so the
    # forward and both cotangent products run one concrete plan
    y = emulated_matmul(x2, w, resolve_for_operands(policy, x2, w))
    return y.reshape(lead + (w.shape[-1],))


def resolve_for_operands(policy: GemmPolicy, x2, w) -> GemmPolicy:
    """`policy` with its adaptive plan resolved for x2 (m, k) @ w (k, n).

    With concrete operands a cheap dynamic-range probe tightens the bound
    (possibly fewer moduli); under jit the probe returns None and the
    static worst case resolves — either way provably within rtol.  A
    policy that is not adaptive comes back as it is.
    """
    if not policy.is_adaptive:
        return policy
    from .accuracy import probe_operands

    return policy.resolve_adaptive(
        x2.shape[0], x2.shape[1], w.shape[-1], stats=probe_operands(x2, w)
    )


def prepare_weights(params, policy: GemmPolicy):
    """Pre-residue-cast every linear weight in a param tree (serving).

    Walks the tree and replaces the ``"w"`` leaf of each linear bundle
    (the dicts produced by `models.layers.linear_abstract`, possibly stacked
    with a leading layers axis for scanned groups, and possibly a list/tuple
    of such stacks) by a right-side `PreparedOperand` cast with the policy's
    *selected execution backend* — so prepared serving stays bit-identical
    to the unprepared run on the kernel path as well as the reference path.
    Step 1 of the scheme then runs once per weight instead of once per
    request.  Fast mode amortizes the whole weight-side cast; accu mode
    amortizes the per-column 7-bit bound matrix and retains the raw weight
    (`keep_raw`) because the accurate exponents couple both operands — the
    weight-side residues are re-cast per call at the coupled truncation
    position (see `PreparedOperand`).  A native policy returns the tree
    unchanged (there is nothing to prepare).
    """
    if policy.backend == "native":
        return params
    if policy.execution == "sharded" or (
        policy.execution == "fused"
        and (policy.mesh is not None or current_mesh() is not None)
    ):
        raise NotImplementedError(
            "prepare_weights under a sharded execution is not supported yet "
            "(prepared planes live unsharded); prepare with "
            "execution='kernel' — or 'fused' outside any mesh scope — "
            "and serve on that policy, or serve unprepared"
        )
    cast_backend = policy.execution_backend()

    def _is_weight_leaf(val):
        return (
            isinstance(val, (jnp.ndarray, np.ndarray))
            and val.ndim >= 2
            and jnp.issubdtype(val.dtype, jnp.inexact)
        )

    def prep(val):
        """Rewrite one "w" value: an array, or a list/tuple of stacked
        weight arrays (scanned groups bundle their per-group stacks this
        way) — the "w" context propagates through the sequence nesting."""
        if _is_weight_leaf(val):
            # adaptive policies resolve statically per weight, with the same
            # canonical pricing shape (m := n) the prepared matmul path uses,
            # so the planes prepared here are exactly what serving resolves
            k, n = int(val.shape[-2]), int(val.shape[-1])
            pol = policy.resolve_adaptive(n, k, n)
            # jnp.asarray: checkpoint restores may hand numpy leaves
            return PreparedOperand(
                jnp.asarray(val).astype(policy.compute_dtype),
                pol.n_moduli
                or default_n_moduli(policy.compute_dtype, pol.mode),
                side="right",
                backend=cast_backend,
                keep_raw=pol.mode == "accu",
            )
        if isinstance(val, (list, tuple)):
            return type(val)(prep(v) for v in val)
        return walk(val)

    def walk(node):
        if isinstance(node, dict):
            return {
                key: (prep(val) if key == "w" else walk(val))
                for key, val in node.items()
            }
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v) for v in node)
        return node

    return walk(params)
