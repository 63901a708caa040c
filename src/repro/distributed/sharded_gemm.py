"""`GemmPolicy(execution="sharded")` — the residue pipeline over the mesh.

Distributes one emulated GEMM over a production mesh by sharding exactly
the axes the scheme makes cheap (ROADMAP "Sharded residue GEMMs"):

* the N residue planes over the `residue` mesh axis (falling back to
  `model`) — each modulus plane is an independent int8 GEMM, so this axis
  is embarrassingly parallel (arXiv:2504.08009);
* output rows m over `data` and columns n over `model`, like a normal GEMM.

K is never sharded.  Each shard casts the operand tiles it consumes itself
(no residue-cast output is ever communicated), runs the UNCHANGED batched
Pallas kernels on its plane chunk — the modulus arrives via scalar
prefetch, so the compiled kernel is modulus-agnostic and takes the shard's
dynamically-sliced chunk — and the only cross-device traffic of the whole
pipeline is ONE psum per output block of the *reconstructed* output in its
exact partial form (never the int8 planes, which at N moduli would be N
bytes/element against `crt_partial_parts(N)` int32 words here but grow with
every operand, not just the output).

Exactness/bitwise contract (the falsifiable part): residue arithmetic is
exact integers end to end, and the partial reconstruction is combined in
the order-independent exact int32 split of `core/crt.partial_split` — each
device psums `sum_{l in chunk} u_{j,l} E_l` part-planes whose every partial
sum is an exact integer below 2^31, then rebuilds the COMPLETE residue
planes locally (`crt.residues_from_partial`) and runs the ordinary Garner
kernel on them.  The sharded output is therefore bitwise identical to the
single-device kernel path on ANY mesh shape, not just numerically close;
the parity suite (tests/test_sharded.py) asserts equality across meshes
and that no int8 array appears in any collective.

Scaling: fast mode is row/column-local, so it needs no communication at
all.  Accurate mode's bound maxima span the full output row/column, so the
per-shard maxima are combined with `lax.pmax` on int32 (exact) over the m/n
mesh axes — wired through the executor's `accu_row_combine`/
`accu_col_combine` backend hooks.

The partial planes are int32, not f64: a TPU emulates f64 with less
precision than the exact-integer window needs.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh

from ..core import crt
from ..core.executor import (
    chunked_residue_matmul,
    execute_plan,
    execute_plan_planar,
)
from ..core.intmul import int8_matmul
from ..core.moduli import CRTContext
from ..core.residues import sym_mod_small
from ..kernels.common import round_up
from .sharding import GemmShardAxes, residue_plane_specs, resolve_gemm_axes

__all__ = ["ShardedBackend"]


class _ShardWorker:
    """The per-shard residue backend one `shard_map` program runs.

    Implements the executor's backend protocol (cast / residue_matmul /
    karatsuba / reconstruct, plus the stacked variants and the accu scale
    combines) for THIS shard's plane chunk, delegating the data-touching
    kernels to the wrapped single-device backend.  Instances close over
    traced values (`lax.axis_index` slices) and live only inside one
    shard_map trace — they are deliberately not hashable/jit-static.
    """

    def __init__(self, inner, ctx: CRTContext, axes: GemmShardAxes, mesh: Mesh):
        self.inner = inner
        self.ctx = ctx
        self.axes = axes
        self.r = mesh.shape[axes.residue] if axes.residue is not None else 1
        # mirror the stacked-launch capabilities so the executor's
        # _cast_pair/_reconstruct_pair take the same single-launch paths
        if hasattr(inner, "cast_stack"):
            self.cast_stack = self._cast_stack
        if hasattr(inner, "reconstruct_stack"):
            self.reconstruct_stack = self._reconstruct_stack
        # megakernel inners run fused per-shard when every shard holds ALL
        # residue planes (r == 1: the moduli stay compile-time static, which
        # the fused Garner epilogue requires); with a sharded residue axis
        # the worker falls back to the composed primitives + the two-phase
        # psum hooks below (the Garner table cannot take a dynamic chunk).
        self.megakernel = self.r == 1 and getattr(inner, "megakernel", False)
        if self.megakernel:
            self.fused_gemm = inner.fused_gemm
            self.fused_karatsuba_gemm = inner.fused_karatsuba_gemm
        if self.r > 1:
            # overlap hooks: the executor issues every block's product, then
            # ONE psum of the collected partial pytree (async-friendly),
            # then the per-block reconstructions
            self.psum_partial = self._psum_partial
            self.psum_combine = self._psum_combine
            self.reconstruct_post = self._reconstruct_post
            self.reconstruct_post_stack = self._reconstruct_post_stack
        # accurate-mode bound maxima must cover the full row/column
        if axes.n is not None:
            self.accu_row_combine = lambda v: lax.pmax(v, axes.n)
        if axes.m is not None:
            self.accu_col_combine = lambda v: lax.pmax(v, axes.m)
        # Pallas-capable inners run the batched kernels with the dynamic
        # modulus chunk; the jnp reference inner gets exact f64 dyn ops
        self._pallas = getattr(inner, "interpret", None) is not None
        if self.r > 1:
            n_pad = round_up(ctx.n, self.r)
            self.chunk = n_pad // self.r
            self.n_pad = n_pad
            # modulus 1 pads: every residue is 0, so padded planes are inert
            mod_pad = np.concatenate(
                [np.asarray(ctx.moduli_arr), np.ones(n_pad - ctx.n, np.int32)]
            )
            u, _, _ = crt.partial_split(ctx.moduli)
            u_pad = np.zeros((u.shape[0], n_pad), np.int32)
            u_pad[:, : ctx.n] = u
            start = lax.axis_index(axes.residue) * self.chunk
            self.mod_loc = lax.dynamic_slice(
                jnp.asarray(mod_pad, jnp.int32), (start,), (self.chunk,)
            )
            self.u_loc = lax.dynamic_slice(
                jnp.asarray(u_pad), (jnp.int32(0), start),
                (u.shape[0], self.chunk),
            )
            pf = self.mod_loc.astype(jnp.float64)
            self._p3 = pf[:, None, None]
            self._half3 = ((pf - 1.0) * 0.5)[:, None, None]

    # ------------------------------------------------------------ casting

    def _slice_planes(self, res, axis):
        """Keep this shard's plane chunk of a full (.., N, ..) residue stack."""
        if self.r == 1:
            return res
        pad = [(0, 0)] * res.ndim
        pad[axis] = (0, self.n_pad - res.shape[axis])
        res = jnp.pad(res, pad)
        return lax.dynamic_slice_in_dim(
            res, lax.axis_index(self.axes.residue) * self.chunk,
            self.chunk, axis,
        )

    def cast(self, x, e, axis, ctx, n_limbs):
        # the shard casts the tile it consumes itself (never communicated);
        # the cast kernel is static over the full moduli tuple, so each
        # shard casts all N planes and keeps its chunk — the cast is the
        # cheap memory-bound stage, and slicing keeps the expensive product
        # and all storage at N/R planes (ROADMAP notes the redundant-cast
        # follow-up).
        return self._slice_planes(self.inner.cast(x, e, axis, ctx, n_limbs), 0)

    def _cast_stack(self, xs, e, axis, ctx, n_limbs):
        return self._slice_planes(
            self.inner.cast_stack(xs, e, axis, ctx, n_limbs), 1
        )

    # ----------------------------------------------------------- products

    def _dyn_mod(self, v):
        """Exact symmetric mod of |v| < 2^44 by this shard's dynamic moduli."""
        return sym_mod_small(v.astype(jnp.float64), self._p3, self._half3)

    def residue_matmul(self, ares, bres, ctx):
        if self.r == 1:
            return self.inner.residue_matmul(ares, bres, ctx)
        if self._pallas:
            from ..kernels.int8_mod_gemm import int8_mod_gemm_batched

            return chunked_residue_matmul(
                lambda a, b, carry: int8_mod_gemm_batched(
                    a, b, moduli=self.mod_loc, carry=carry,
                    interpret=self.inner.interpret,
                ),
                ares, bres, ctx, carry_epilogue=True,
            )

        def gemm(a, b, carry):
            d = int8_matmul(a, b).astype(jnp.float64)
            if carry is not None:
                d = d + carry.astype(jnp.float64)
            return self._dyn_mod(d).astype(jnp.int8)

        return chunked_residue_matmul(gemm, ares, bres, ctx, carry_epilogue=True)

    def karatsuba(self, arr, ari, brr, bri, ctx):
        if self.r == 1:
            return self.inner.karatsuba(arr, ari, brr, bri, ctx)
        if self._pallas:
            from ..kernels.karatsuba_fused import karatsuba_mod_gemm_batched

            return chunked_residue_matmul(
                lambda a, b, carry: karatsuba_mod_gemm_batched(
                    a[0], a[1], b[0], b[1], moduli=self.mod_loc, carry=carry,
                    interpret=self.inner.interpret,
                ),
                (arr, ari), (brr, bri), ctx, carry_epilogue=True,
            )
        # jnp reference flavour: compose the D/E/F triple with dynamic mods
        asum = self._dyn_mod(
            arr.astype(jnp.int32) + ari.astype(jnp.int32)
        ).astype(jnp.int8)
        bsum = self._dyn_mod(
            brr.astype(jnp.int32) + bri.astype(jnp.int32)
        ).astype(jnp.int8)
        d = self.residue_matmul(arr, brr, ctx).astype(jnp.int32)
        e = self.residue_matmul(ari, bri, ctx).astype(jnp.int32)
        f = self.residue_matmul(asum, bsum, ctx).astype(jnp.int32)
        er = self._dyn_mod(d - e).astype(jnp.int8)
        ei = self._dyn_mod(f - d - e).astype(jnp.int8)
        return er, ei

    # ------------------------------------------------------ reconstruction

    def _full_planes(self, e_res, ctx, stacked: bool):
        """Local plane chunk -> COMPLETE (.., N, m, n) residue planes.

        The single communication point: psum the exact int32 partial planes
        over the residue axis (bitwise order-independent by construction),
        then rebuild all N residues locally.
        """
        t = crt.partial_combine(e_res, self.u_loc)
        t = lax.psum(t, self.axes.residue)
        if not stacked:
            return crt.residues_from_partial(t, ctx)
        planes = crt.residues_from_partial(jnp.moveaxis(t, 0, 1), ctx)
        return jnp.moveaxis(planes, 0, 1)

    # -- two-phase psum hooks (r > 1): the executor's blocked pipelines
    # issue ALL blocks' products before any collective, psum the collected
    # partial pytree ONCE, then rebuild + reconstruct per block — so the
    # only cross-device traffic of the pipeline is one async-overlappable
    # collective instead of one serialized psum between consecutive blocks.
    # Bitwise identical to the per-block `_full_planes` route: a pytree
    # psum is the same per-leaf reduction of exact int32 partials.

    def _psum_partial(self, e_res):
        """Local (.., N_loc, m, n) plane chunk -> exact int32 partial planes
        (NO collective — collected by the executor across blocks)."""
        return crt.partial_combine(e_res, self.u_loc)

    def _psum_combine(self, partials, stacked: bool = False):
        """ONE psum of all blocks' partials, then rebuild the COMPLETE
        (.., N, m, n) residue planes of every block locally."""
        partials = lax.psum(partials, self.axes.residue)
        out = []
        for t in partials:
            if stacked:
                planes = crt.residues_from_partial(
                    jnp.moveaxis(t, 0, 1), self.ctx
                )
                out.append(jnp.moveaxis(planes, 0, 1))
            else:
                out.append(crt.residues_from_partial(t, self.ctx))
        return out

    def _reconstruct_post(self, e_res, e_mu, e_nu, ctx, method, out_dtype):
        """Reconstruct from already-complete planes (post `psum_combine`)."""
        return self.inner.reconstruct(e_res, e_mu, e_nu, ctx, method, out_dtype)

    def _reconstruct_post_stack(self, e_res, e_mu, e_nu, ctx, method, out_dtype):
        rec = getattr(self.inner, "reconstruct_stack", None)
        if rec is None:
            return (
                self.inner.reconstruct(e_res[0], e_mu, e_nu, ctx, method, out_dtype),
                self.inner.reconstruct(e_res[1], e_mu, e_nu, ctx, method, out_dtype),
            )
        return rec(e_res, e_mu, e_nu, ctx, method, out_dtype)

    def reconstruct(self, e_res, e_mu, e_nu, ctx, method, out_dtype):
        if self.r > 1:
            e_res = self._full_planes(e_res, ctx, stacked=False)
        return self.inner.reconstruct(e_res, e_mu, e_nu, ctx, method, out_dtype)

    def _reconstruct_stack(self, e_res, e_mu, e_nu, ctx, method, out_dtype):
        if self.r > 1:
            e_res = self._full_planes(e_res, ctx, stacked=True)
        return self.inner.reconstruct_stack(
            e_res, e_mu, e_nu, ctx, method, out_dtype
        )


@dataclasses.dataclass(frozen=True)
class ShardedBackend:
    """Residue backend running the plan under `shard_map` over `mesh`.

    Hashable (rides in jit-static slots like every backend); the per-shard
    worker is built inside the traced program.  `shard_axes` is the
    policy's explicit (residue, m, n) axis-name override, None = resolve
    per `distributed.sharding.resolve_gemm_axes`.
    """

    inner: Any
    mesh: Mesh
    shard_axes: tuple | None = None

    # plan 'auto' selections charge launches as the per-shard inner does
    @property
    def fused_karatsuba(self) -> bool:
        return getattr(self.inner, "fused_karatsuba", False)

    @property
    def modulus_batched(self) -> bool:
        return getattr(self.inner, "modulus_batched", False)

    @property
    def megakernel(self) -> bool:
        # advertised for plan pricing; per-shard workers actually run fused
        # only when the residue axis is unsharded (r == 1, static moduli)
        return getattr(self.inner, "megakernel", False)

    @property
    def uses_pallas(self) -> bool:
        return getattr(self.inner, "uses_pallas", True)

    def analyze(self, plan, shape=None):
        """Static-analysis suite certifying the sharded pipeline: the
        collective-safety pass is the load-bearing one here (only exact
        int32 CRT partials may psum), and the launch-count certificate is
        derived from `shard_factors` (the fused worker engages only on
        m/n-only meshes).  See repro.analysis.passes_for_backend."""
        from ..analysis import passes_for_backend

        return passes_for_backend(self, plan, shape)

    def resolve_axes(self, m: int, n: int) -> GemmShardAxes:
        return resolve_gemm_axes(self.mesh, m, n, self.shard_axes)

    def shard_factors(self, m: int, n: int) -> tuple[int, int, int]:
        """(m_shards, n_shards, residue_shards) actually applied at (m, n) —
        consulted by `GemmPolicy.plan_for` so the perfmodel-driven 'auto'
        selections price the per-shard problem plus the psum term."""
        axes = self.resolve_axes(m, n)
        r, md, nd = axes.sizes(self.mesh)
        return md, nd, r

    def run_plan(self, plan, a, b):
        """Execute `plan` on (m, k) x (k, n) sharded over the mesh."""
        return self._run(execute_plan, plan, a, b)

    def run_plan_planar(self, plan, ar, ai, br, bi):
        """`run_plan` for a complex plan on planar operands -> (cr, ci)."""
        return self._run(execute_plan_planar, plan, ar, ai, br, bi)

    def _run(self, execute, plan, *ops):
        """shard_map `execute(plan, *A parts, *B parts, worker)` over the
        mesh; the first half of `ops` are A's parts, the rest B's."""
        half = len(ops) // 2
        a, b = ops[0], ops[half]
        if getattr(a, "ndim", 0) != 2 or getattr(b, "ndim", 0) != 2:
            raise ValueError(
                "sharded execution supports 2D operands; reshape leading "
                "batch dims into rows (policy_matmul does) — got "
                f"{getattr(a, 'shape', None)} @ {getattr(b, 'shape', None)}"
            )
        axes = self.resolve_axes(a.shape[0], b.shape[1])
        specs = residue_plane_specs(axes)

        def body(*local):
            worker = _ShardWorker(self.inner, plan.ctx, axes, self.mesh)
            return execute(plan, *local, worker)

        # one output, or the planar (cr, ci) pair
        out_specs = specs["out"] if half == 1 else (specs["out"],) * 2
        fn = shard_map(
            body,
            mesh=self.mesh,
            in_specs=(specs["a"],) * half + (specs["b"],) * half,
            out_specs=out_specs,
            check_vma=False,
        )
        return fn(*ops)
