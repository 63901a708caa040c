"""Jaxpr-level analysis passes certifying the residue pipeline's invariants.

Each pass is a small object with a ``name`` and a ``run(jaxpr) ->
list[Finding]`` method; ``jaxpr`` is whatever `jax.make_jaxpr` returned (a
ClosedJaxpr) or any open Jaxpr.  An empty list is a certificate; a finding
names the violated invariant and where it was found.  The passes:

``OverflowPass``
    The paper SIII-A accumulation bound, proved from shapes/dtypes/consts
    of the traced program instead of trusted from the chunking code:

    * every `dot_general` whose operands are int8 residue planes must have
      effective contraction length <= ``K_CHUNK_LIMIT`` (2^17): with
      |residue| <= 127 the int32 accumulator stays < 2^31, so no silent
      wraparound.  Inside a `pallas_call` the *effective* contraction is
      the per-block contraction times the innermost grid axis, because all
      of this repo's mod-GEMM kernels iterate K as the last grid dimension
      and accumulate in scratch across it.
    * every fp8 (float8_e4m3*) dot must have effective contraction
      <= 2 * ``FP8_K_CHUNK_LIMIT``: balanced base-16 digits are bounded by
      8, so digit products are <= 64 and eff_k * 64 <= 2^23 keeps the f32
      accumulator exact (< 2^24).  The factor 2 admits the Karatsuba /
      cross-term dots, which concatenate two digit planes along K.
    * every f64 `dot_general` whose operand magnitudes are *provable*
      (from consts, or int8/fp8 inputs converted to f64) must satisfy
      |lhs| * |rhs| * eff_k <= 2^53 — the exact-f64-integer window.
      Unprovable f64/f32/bf16
      dots are out of scope (ordinary float compute) and never flagged.

``CollectiveSafetyPass``
    No low-precision array may cross the mesh: any collective
    (psum/pmax/pmin/all_gather/all_to_all/ppermute/reduce_scatter/...)
    with an operand dtype narrower than 4 bytes is a finding.  The sharded
    pipeline's contract is that only exact int32 CRT partials (and int32
    exponent scalars) are communicated.

``LaunchCountPass``
    `pallas_call` eqn count must equal the perfmodel's
    ``kernel_launch_count(...)`` for the policy under analysis (use
    :func:`expected_launch_count` to derive the expectation from a
    backend + plan + shape).

``AccuracyPass``
    A plan carrying a declared tolerance (``EmulationPlan.rtol``, stamped
    by `GemmPolicy(rtol=...)` / ``mode="auto"``) must *provably* meet it:
    the static `core.accuracy.rel_bound` for (dtype, mode, n_moduli, k,
    formulation) must be <= the declared rtol.  Static check — the traced
    jaxpr is not consulted (quantization is the only inexact step and every
    execution is bitwise-identical to the reference, so the bound depends
    only on the plan), but the pass runs in the same suite so a
    ``--matrix`` row with an rtol column is certified alongside its
    overflow/launch invariants.

``ScanIndexWidthPass``
    Flags s64 indices feeding `dynamic_slice` / `dynamic_update_slice` /
    `gather` / `scatter*` inside `scan` bodies — the exact SPMD
    partitioner-crash bug class fixed by hand in PRs 5 and 6 (a Python-int
    carry index weakly typed to int64 under x64).  The fix is always an
    explicit ``jnp.int32`` index.

:func:`passes_for_backend` assembles the suite for a residue backend (the
``analyze(plan, shape)`` hook on every backend delegates here), and
:func:`certify_partial_split` statically certifies the CRT partial-split
tables themselves.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .jaxprs import EqnContext, count_primitive, iter_eqns, unwrap

__all__ = [
    "Finding",
    "AccuracyPass",
    "OverflowPass",
    "CollectiveSafetyPass",
    "LaunchCountPass",
    "ScanIndexWidthPass",
    "COLLECTIVE_PRIMS",
    "collect_collectives",
    "certify_partial_split",
    "certify_launch_count",
    "expected_launch_count",
    "passes_for_backend",
    "run_passes",
]


@dataclasses.dataclass(frozen=True)
class Finding:
    """One violated invariant found by a pass.

    ``pass_name``  the pass that produced it;
    ``message``    human-readable description naming the bound violated;
    ``primitive``  the jaxpr primitive at fault (None for static checks);
    ``path``       enclosing primitive names, outermost first.
    """

    pass_name: str
    message: str
    primitive: str | None = None
    path: tuple = ()

    def __str__(self) -> str:
        where = "/".join(self.path + ((self.primitive,) if self.primitive else ()))
        return f"[{self.pass_name}] {where or '<static>'}: {self.message}"


class JaxprPass:
    """Base class: iterate every (eqn, context) and collect findings."""

    name = "pass"

    def run(self, jaxpr) -> list:
        findings: list[Finding] = []
        for eqn, ctx in iter_eqns(jaxpr):
            self.visit(eqn, ctx, findings)
        return findings

    def visit(self, eqn, ctx: EqnContext, findings: list) -> None:
        raise NotImplementedError


def _default_k_limit() -> int:
    from ..core.moduli import K_CHUNK_LIMIT

    return K_CHUNK_LIMIT


def _default_fp8_limit() -> int:
    try:
        from ..kernels.fp8_mod_gemm import FP8_K_CHUNK_LIMIT

        return FP8_K_CHUNK_LIMIT
    except Exception:  # pragma: no cover - fp8 kernels unavailable
        return 1 << 16


def _abs_bound(val) -> float | None:
    """max|val| for a concrete numeric array, None if not provable."""
    try:
        arr = np.asarray(val)
    except Exception:
        return None
    if arr.size == 0:
        return 0.0
    if arr.dtype.kind not in "iufb":
        return None
    arr = arr.astype(np.float64)
    if not np.all(np.isfinite(arr)):
        return None
    return float(np.max(np.abs(arr)))


# dtype-derived magnitude bounds: int8 residue planes are symmetric residues
# (|r| <= 127 by construction, and 127 is the dtype bound anyway); fp8 e4m3
# operands in this codebase are balanced base-16 digits, |d| <= 8 — that
# invariant comes from kernels/fp8_mod_gemm._digits and is assumed here.
_FP8_DIGIT_BOUND = 8.0


def _dtype_bound(dtype) -> float | None:
    dt = np.dtype(dtype) if not hasattr(dtype, "kind") else dtype
    name = getattr(dt, "name", str(dt))
    if name == "int8":
        return 127.0
    if name == "uint8":
        return 255.0
    if name == "bool":
        return 1.0
    if name.startswith("float8"):
        return _FP8_DIGIT_BOUND
    return None


def _is_int8(dtype) -> bool:
    name = getattr(dtype, "name", str(dtype))
    return name in ("int8", "uint8")


def _is_fp8(dtype) -> bool:
    name = getattr(dtype, "name", str(dtype))
    return name.startswith("float8")


@dataclasses.dataclass(frozen=True)
class OverflowPass:
    """Overflow/exactness certifier (paper SIII-A accumulation bound)."""

    k_limit: int | None = None
    fp8_limit: int | None = None
    f64_exact: float = 2.0**53

    name = "overflow"

    def run(self, jaxpr) -> list:
        open_jaxpr, consts = unwrap(jaxpr)
        findings: list[Finding] = []
        k_limit = self.k_limit if self.k_limit is not None else _default_k_limit()
        fp8_limit = (
            self.fp8_limit if self.fp8_limit is not None else _default_fp8_limit()
        )
        self._walk(open_jaxpr, consts, None, (), k_limit, fp8_limit, findings)
        return findings

    # -- bound environment ------------------------------------------------
    @staticmethod
    def _bound_of(atom, bounds: dict) -> float | None:
        if hasattr(atom, "val"):  # Literal
            return _abs_bound(atom.val)
        try:
            if atom in bounds:
                return bounds[atom]
        except TypeError:  # unhashable atom
            pass
        aval = getattr(atom, "aval", None)
        dt = getattr(aval, "dtype", None)
        return _dtype_bound(dt) if dt is not None else None

    # propagation through shape/dtype-preserving ops keeps bounds provable
    # across the convert-to-f64 step in front of the CRT partial dots
    _PRESERVING = frozenset(
        {
            "convert_element_type",
            "reshape",
            "transpose",
            "broadcast_in_dim",
            "squeeze",
            "expand_dims",
            "slice",
            "dynamic_slice",
            "rev",
            "neg",
            "abs",
            "copy",
            "device_put",
            "stop_gradient",
            "reduce_precision",
        }
    )

    def _walk(self, jaxpr, consts, grid, path, k_limit, fp8_limit, findings):
        bounds: dict = {}
        if consts is not None:
            for var, val in zip(jaxpr.constvars, consts):
                b = _abs_bound(val)
                if b is not None:
                    bounds[var] = b

        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim == "dot_general":
                self._check_dot(
                    eqn, bounds, grid, path, k_limit, fp8_limit, findings
                )
            elif prim in self._PRESERVING and eqn.invars:
                b = self._bound_of(eqn.invars[0], bounds)
                if b is not None:
                    bounds[eqn.outvars[0]] = b
            elif prim == "concatenate":
                bs = [self._bound_of(v, bounds) for v in eqn.invars]
                if all(b is not None for b in bs):
                    bounds[eqn.outvars[0]] = max(bs)

            # recurse into nested jaxprs (pjit/shard_map/scan/cond/pallas)
            sub_grid = grid
            if prim == "pallas_call":
                from .jaxprs import pallas_grid

                sub_grid = pallas_grid(eqn.params)
            from .jaxprs import _closed_subjaxprs

            for v in eqn.params.values():
                for sub, sub_consts in _closed_subjaxprs(v):
                    self._walk(
                        sub,
                        sub_consts,
                        sub_grid,
                        path + (prim,),
                        k_limit,
                        fp8_limit,
                        findings,
                    )

    def _check_dot(self, eqn, bounds, grid, path, k_limit, fp8_limit, findings):
        lhs, rhs = eqn.invars[:2]
        (lhs_contract, _), _ = eqn.params["dimension_numbers"]
        contraction = 1
        for axis in lhs_contract:
            contraction *= int(lhs.aval.shape[axis])
        # inside a pallas kernel the innermost grid axis accumulates into
        # scratch across steps (K is always the last grid dim in this
        # repo's mod-GEMM kernels) — that is the true contraction length
        eff = contraction * (grid[-1] if grid else 1)
        ldt = lhs.aval.dtype
        rdt = rhs.aval.dtype

        if _is_int8(ldt) and _is_int8(rdt):
            if eff > k_limit:
                findings.append(
                    Finding(
                        self.name,
                        f"int8 dot_general accumulates effective K={eff} > "
                        f"K_CHUNK_LIMIT={k_limit}; 127^2 * K no longer fits "
                        "the exact int32 window (paper SIII-A bound)",
                        primitive="dot_general",
                        path=path,
                    )
                )
        elif _is_fp8(ldt) and _is_fp8(rdt):
            if eff > 2 * fp8_limit:
                findings.append(
                    Finding(
                        self.name,
                        f"fp8 dot_general accumulates effective K={eff} > "
                        f"2*FP8_K_CHUNK_LIMIT={2 * fp8_limit}; digit products "
                        "(<=64) would leave the exact f32 window (2^24)",
                        primitive="dot_general",
                        path=path,
                    )
                )
        else:
            out_dt = eqn.outvars[0].aval.dtype
            if getattr(out_dt, "name", str(out_dt)) == "float64":
                lb = self._bound_of(lhs, bounds)
                rb = self._bound_of(rhs, bounds)
                if lb is not None and rb is not None:
                    worst = lb * rb * eff
                    if worst > self.f64_exact:
                        findings.append(
                            Finding(
                                self.name,
                                f"f64 dot_general partial sum bounded by "
                                f"{lb:g} * {rb:g} * K={eff} = {worst:.3g} > "
                                "2^53: the f64 sum would round",
                                primitive="dot_general",
                                path=path,
                            )
                        )


#: collective primitives whose operands cross the mesh (jaxpr-level names)
COLLECTIVE_PRIMS = frozenset(
    {
        "psum",
        "psum2",
        "psum_invariant",  # psum under shard_map(check_vma=True)
        "pmax",
        "pmin",
        "pmean",
        "all_gather",
        "all_reduce",
        "all_to_all",
        "ppermute",
        "pbroadcast",
        "reduce_scatter",
    }
)


@dataclasses.dataclass(frozen=True)
class CollectiveSafetyPass(JaxprPass):
    """No int8/fp8/low-precision array may flow into a collective."""

    min_itemsize: int = 4

    name = "collective-safety"

    def visit(self, eqn, ctx, findings):
        if eqn.primitive.name not in COLLECTIVE_PRIMS:
            return
        for v in eqn.invars:
            dt = getattr(getattr(v, "aval", None), "dtype", None)
            if dt is None:
                continue
            if np.dtype(dt).itemsize < self.min_itemsize:
                findings.append(
                    Finding(
                        self.name,
                        f"{dt} array crosses the mesh via "
                        f"`{eqn.primitive.name}`; only exact int32 CRT "
                        "partials (and >=32-bit scalars) may be "
                        "communicated",
                        primitive=eqn.primitive.name,
                        path=ctx.path,
                    )
                )


def collect_collectives(jaxpr) -> list:
    """All collective eqns in `jaxpr` as (primitive_name, [operand dtypes]).

    Positive-evidence helper for tests: e.g. assert an int32 psum exists in a
    sharded trace (the CollectiveSafetyPass alone would also pass on a
    program with no communication at all).
    """
    out = []
    for eqn, _ in iter_eqns(jaxpr):
        if eqn.primitive.name in COLLECTIVE_PRIMS:
            dtypes = [
                getattr(getattr(v, "aval", None), "dtype", None)
                for v in eqn.invars
            ]
            out.append((eqn.primitive.name, dtypes))
    return out


@dataclasses.dataclass(frozen=True)
class LaunchCountPass:
    """pallas_call count must equal the perfmodel's prediction."""

    expected: int

    name = "launch-count"

    def run(self, jaxpr) -> list:
        open_jaxpr, _ = unwrap(jaxpr)
        got = count_primitive(open_jaxpr, "pallas_call")
        if got != self.expected:
            return [
                Finding(
                    self.name,
                    f"traced program has {got} pallas_call launches, "
                    f"perfmodel.kernel_launch_count predicts {self.expected}",
                    primitive="pallas_call",
                )
            ]
        return []


@dataclasses.dataclass(frozen=True)
class AccuracyPass:
    """The plan's static error bound must meet its declared tolerance.

    ``plan`` is the :class:`~repro.core.plan.EmulationPlan` under analysis
    and ``k`` the contraction length of the certified GEMM; ``rtol``
    defaults to the plan's own declared contract (``plan.rtol``).  The
    check is `core.accuracy.rel_bound(...) <= rtol` — purely static, since
    quantization is the scheme's only inexact step and every execution
    backend is bitwise-identical to the reference (PR 5/6 invariant), so
    the componentwise bound depends on the plan alone, not the trace.
    A plan with no declared rtol trivially certifies (empty suite result).
    """

    plan: object
    k: int
    rtol: float | None = None

    name = "accuracy"

    def run(self, jaxpr) -> list:
        del jaxpr  # static check; see class docstring
        rtol = self.rtol if self.rtol is not None else self.plan.rtol
        if rtol is None:
            return []
        from ..core.accuracy import rel_bound

        bound = rel_bound(
            self.plan.dtype,
            self.plan.mode,
            self.plan.n_moduli,
            int(self.k),
            formulation=self.plan.formulation,
            out_dtype=self.plan.out_dtype,
        )
        if bound > rtol:
            return [
                Finding(
                    self.name,
                    f"plan ({self.plan.dtype}, mode={self.plan.mode}, "
                    f"N={self.plan.n_moduli}, {self.plan.formulation}) has "
                    f"static componentwise bound {bound:.3g} at k={self.k} "
                    f"> declared rtol={rtol:.3g}",
                )
            ]
        return []


# primitives that consume index operands, and which invars are indices
_INDEXED_PRIMS = {
    "dynamic_slice": slice(1, None),
    "dynamic_update_slice": slice(2, None),
    "gather": slice(1, 2),
    "scatter": slice(1, 2),
    "scatter-add": slice(1, 2),
    "scatter-mul": slice(1, 2),
    "scatter-min": slice(1, 2),
    "scatter-max": slice(1, 2),
}


@dataclasses.dataclass(frozen=True)
class ScanIndexWidthPass(JaxprPass):
    """No s64 index may feed indexing primitives inside a scan body.

    Under x64 (this repo enables it globally for the f64 CRT arithmetic) a
    Python-int scan carry weakly types to int64; an s64 index feeding
    dynamic_slice/gather inside the scanned body crashes the SPMD
    partitioner on sharded meshes (the PR 5/6 bug class).  Use
    ``jnp.int32`` indices in scan carries.
    """

    name = "scan-index-width"

    def visit(self, eqn, ctx, findings):
        idx = _INDEXED_PRIMS.get(eqn.primitive.name)
        if idx is None or not ctx.in_scan_body:
            return
        for v in eqn.invars[idx]:
            dt = getattr(getattr(v, "aval", None), "dtype", None)
            if dt is not None and getattr(dt, "name", str(dt)) == "int64":
                findings.append(
                    Finding(
                        self.name,
                        f"int64 index feeds `{eqn.primitive.name}` inside a "
                        "scan body; use an explicit jnp.int32 index (s64 "
                        "scan-carried indices crash the SPMD partitioner "
                        "under x64)",
                        primitive=eqn.primitive.name,
                        path=ctx.path,
                    )
                )


def certify_partial_split(moduli, u=None, part_bits=None) -> list:
    """Statically certify the CRT partial-split tables for `moduli`.

    Checks (see core/crt.partial_split): every entry of the combine table
    ``u`` is a nonnegative integer below ``2**part_bits``, and the worst
    partial sum ``max(u) * 127 * N`` stays within int32 (below 2^31) — so
    `partial_combine`'s int32 multiply-adds are exact for any residue
    inputs.  Pass `u` / `part_bits` explicitly to audit a foreign
    table; by default the tables are recomputed from `moduli`.
    """
    from ..core import crt

    moduli = tuple(int(q) for q in moduli)
    if u is None or part_bits is None:
        u_tab, _, pb = crt.partial_split(moduli)
        u = u_tab if u is None else u
        part_bits = pb if part_bits is None else part_bits
    u = np.asarray(u, dtype=np.float64)
    n = len(moduli)
    findings: list[Finding] = []
    name = "overflow"
    if np.any(u < 0) or np.any(u != np.floor(u)):
        findings.append(
            Finding(name, "partial-split table u has non-integer or negative "
                          "entries; the reconstruction is not exact")
        )
    if u.size and float(np.max(u)) >= 2.0 ** int(part_bits):
        findings.append(
            Finding(
                name,
                f"partial-split table entry {np.max(u):.0f} >= 2^part_bits="
                f"2^{part_bits}; parts are wider than the split claims",
            )
        )
    worst = (float(np.max(u)) if u.size else 0.0) * 127.0 * n
    if worst >= 2.0**31:
        findings.append(
            Finding(
                name,
                f"worst CRT partial sum max(u)*127*N = {worst:.3g} >= 2^31; "
                "partial_combine's int32 accumulation would overflow",
            )
        )
    return findings


def expected_launch_count(backend, plan, shape, prepared: bool = False):
    """perfmodel launch-count prediction for `backend` executing `plan` at
    ``shape = (m, k, n)``; None when no static prediction applies."""
    from ..core import perfmodel

    m, k, n = shape
    if not getattr(backend, "uses_pallas", True):
        return 0
    engine = getattr(backend, "engine", "int8")
    chunk_limit = _default_fp8_limit() if engine == "fp8" else _default_k_limit()
    fused = bool(getattr(backend, "megakernel", False))
    shard_factors = getattr(backend, "shard_factors", None)
    n_local = n
    if callable(shard_factors):
        _, nd, r = shard_factors(m, n)
        n_local = -(-n // nd)
        # the sharded fused worker only engages on m/n-only meshes; on a
        # residue mesh it falls back to the composed kernel pipeline
        fused = fused and r == 1
    n_chunks = max(1, -(-k // chunk_limit))
    n_blocks = len(list(plan.n_block_slices(n_local)))
    formulation = plan.formulation if plan.is_complex else "real"
    return perfmodel.kernel_launch_count(
        plan.n_moduli,
        formulation,
        modulus_batched=getattr(backend, "modulus_batched", False),
        fused_karatsuba=getattr(backend, "fused_karatsuba", False),
        n_chunks=n_chunks,
        n_blocks=n_blocks,
        prepared=prepared,
        fused=fused,
    )


def certify_launch_count(expected: int, fn, *args, **kwargs) -> list:
    """Trace fn(*args, **kwargs) and run LaunchCountPass(expected) on it."""
    import jax

    jaxpr = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    return LaunchCountPass(expected=expected).run(jaxpr)


def passes_for_backend(backend, plan, shape=None) -> tuple:
    """The analysis suite certifying `backend` executing `plan`.

    Always includes the overflow, collective-safety, and scan-index-width
    passes (with the chunk limits of the backend's engine); when `shape`
    is given, also a LaunchCountPass pinned to the perfmodel prediction
    and — for a plan declaring an accuracy contract (``plan.rtol``) — an
    AccuracyPass certifying the static bound at the shape's contraction
    length.  Backends expose this as ``backend.analyze(plan, shape)``.
    """
    passes = [
        OverflowPass(
            k_limit=_default_k_limit(), fp8_limit=_default_fp8_limit()
        ),
        CollectiveSafetyPass(),
        ScanIndexWidthPass(),
    ]
    if shape is not None:
        expected = expected_launch_count(backend, plan, shape)
        if expected is not None:
            passes.append(LaunchCountPass(expected=expected))
        if getattr(plan, "rtol", None) is not None:
            passes.append(AccuracyPass(plan=plan, k=shape[1]))
    return tuple(passes)


def run_passes(passes, jaxpr) -> list:
    """Run every pass over `jaxpr`, concatenating findings."""
    findings: list[Finding] = []
    for p in passes:
        findings.extend(p.run(jaxpr))
    return findings
