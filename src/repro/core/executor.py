"""The single executor for Ozaki-II emulation plans (real and complex).

One code path drives Alg. 1 for every public entry point:

    scale -> quantize -> residue-cast -> per-modulus int8 GEMMs
          -> CRT reconstruct -> exact inverse scaling

parameterized by an :class:`EmulationPlan` (static decisions) and a
*residue backend* supplying the three data-touching primitives:

  cast(x, e, axis)            scale+trunc+limb-split -> (N, ...) int8 residues
  residue_matmul(ares, bres)  (N,m,k) x (N,k,n) -> (N,m,n) int8 residues
  karatsuba(arr, ari, brr, bri)  fused complex residue product (3 GEMMs)
  reconstruct(e_res, e_mu, e_nu, method, out_dtype)  CRT + inverse scaling

plus two OPTIONAL stacked variants — `cast_stack` / `reconstruct_stack`
operating on an (S, ...) leading stack that shares scale exponents — which
the complex pipeline uses (via `_cast_pair` / `_reconstruct_pair`) to cast
and reconstruct real/imag parts together; backends without them (the
reference and per-modulus kernel backends) transparently fall back to two
calls with bitwise-identical results.

`ReferenceBackend` is the jnp path (exact f64 host arithmetic, all three CRT
methods); `repro.kernels.ops.KernelBackend` is the Pallas TPU path.  The two
block-embedding formulations (paper eqs. 7/8) are composed here from
`residue_matmul`, so any backend gets all three Fig. 1 strategies for free.

Everything is jit-compatible: plans and backends are static (hashable), and
batching over leading operand dims is provided by `run_plan`.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from . import crt, scaling
from .intmul import int8_matmul
from .moduli import CRTContext, K_CHUNK_LIMIT, make_crt_context
from .plan import EmulationPlan, make_plan, n_limbs_for_ctx
from .residues import quantize, residues_from_quantized, sym_mod_int32

#: The pipeline's stages.  Each runs under `stage(name)`, a
#: `jax.named_scope("ozaki2.<name>")`, so the `op_name` metadata of the
#: compiled program and a profile of it name the stage of every op:
#:
#:   scale     the scale exponents (`scaling.scale_*`, the accu bounds)
#:   cast      the residue casts (`backend.cast`, `_cast_pair`)
#:   product   the residue products, eq. 7/8 embeddings included
#:   garner    CRT reconstruction and inverse scaling (`reconstruct*`)
#:   assemble  the planar split, the complex output, n-block slices and
#:             their concatenation
#:   psum      the sharded partial combine (`psum_partial`, `psum_combine`)
#:   fused     a megakernel launch (cast, products and Garner in one)
#:
#: The pads and slices a backend wraps around a kernel run inside the
#: kernel's stage.  Where stages nest, the innermost names the op.  Scopes
#: are trace-time metadata: the compiled program is the same but for names.
STAGES = ("scale", "cast", "product", "garner", "assemble", "psum", "fused")


def stage(name: str):
    """The named scope of one pipeline stage (a context manager, or a
    function decorator)."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; stages are {STAGES}")
    return jax.named_scope(f"ozaki2.{name}")


def _sym_mod_stack(d: jnp.ndarray, ctx: CRTContext) -> jnp.ndarray:
    outs = [sym_mod_int32(d[l], int(ctx.moduli_arr[l])) for l in range(ctx.n)]
    return jnp.stack(outs, axis=0)


def chunked_residue_matmul(
    mod_gemm_stack, ares, bres, ctx: CRTContext, carry_epilogue: bool = False,
    chunk_limit: int | None = None,
):
    """K-chunk an (N,m,k)x(N,k,n) residue product so every engine GEMM
    accumulates exactly (k <= `chunk_limit` per call), reducing mod p
    between chunks (residue arithmetic is closed).  `chunk_limit` defaults
    to the int8 engine's int32 bound (`K_CHUNK_LIMIT`, 2^17 — resolved at
    call time, so tests can patch the module constant); the fp8 engine
    passes its tighter f32 digit-accumulator bound (`FP8_K_CHUNK_LIMIT`,
    2^16).

    Two chunk-combine strategies share this single implementation of the
    chunking invariant:

      * ``carry_epilogue=False`` — `mod_gemm_stack(ares, bres) -> (N,m,n)
        int8`: chunk residues are summed as int32 host-side and reduced once
        (the jnp reference path).
      * ``carry_epilogue=True`` — `mod_gemm_stack(ares, bres, carry) ->
        (N,m,n) int8`: the previous chunk's residues are threaded through the
        backend's carry input and folded into its *kernel epilogue* mod, so
        on the kernel path chunked-K stays one batched launch per chunk with
        no host-side per-modulus loop.  On this path `ares`/`bres` (and the
        carry) may be pytrees of same-K stacks — the fused-Karatsuba product
        passes its (R, I) plane pairs and carries (CR, CI) — keeping this
        loop the ONLY implementation of the chunk limit.

    Both produce the exact canonical symmetric residues of the full-k
    product, hence bitwise-identical outputs; the stacked planes pass
    through unchanged either way.
    """
    if chunk_limit is None:
        chunk_limit = K_CHUNK_LIMIT
    if carry_epilogue:
        k = jax.tree.leaves(ares)[0].shape[-1]
        carry = None
        for k0 in range(0, k, chunk_limit):
            sl = slice(k0, k0 + chunk_limit)
            carry = mod_gemm_stack(
                jax.tree.map(lambda x: x[..., sl], ares),
                jax.tree.map(lambda x: x[:, sl, :], bres),
                carry,
            )
        return carry
    k = ares.shape[-1]
    if k <= chunk_limit:
        return mod_gemm_stack(ares, bres)
    acc = None
    for k0 in range(0, k, chunk_limit):
        e = mod_gemm_stack(
            ares[..., k0 : k0 + chunk_limit],
            bres[:, k0 : k0 + chunk_limit, :],
        ).astype(jnp.int32)
        acc = e if acc is None else acc + e
    # |acc| <= n_chunks*127 << 2^31
    return _sym_mod_stack(acc, ctx).astype(jnp.int8)


@stage("cast")
def _cast(backend, x, e, axis, ctx, n_limbs):
    """Residue-cast one real operand."""
    return backend.cast(x, e, axis, ctx, n_limbs)


@stage("cast")
def _cast_pair(backend, xr, xi, e, axis, ctx, n_limbs):
    """Residue-cast a real/imag pair sharing one scale vector.

    Backends exposing `cast_stack` (the batched kernel path) cast both parts
    in a single launch; others fall back to two `cast` calls.  Bitwise
    identical either way (the stacked kernel runs the same per-part math).
    """
    cast_stack = getattr(backend, "cast_stack", None)
    if cast_stack is None:
        return (
            backend.cast(xr, e, axis, ctx, n_limbs),
            backend.cast(xi, e, axis, ctx, n_limbs),
        )
    res = cast_stack(jnp.stack([xr, xi]), e, axis, ctx, n_limbs)
    return res[0], res[1]


@stage("garner")
def _reconstruct_pair(backend, er, ei, e_mu, e_nu, ctx, method, out_dtype):
    """Reconstruct a CR/CI residue pair (one stacked launch when the backend
    provides `reconstruct_stack`, else two `reconstruct` calls)."""
    rec_stack = getattr(backend, "reconstruct_stack", None)
    if rec_stack is None:
        return (
            backend.reconstruct(er, e_mu, e_nu, ctx, method, out_dtype),
            backend.reconstruct(ei, e_mu, e_nu, ctx, method, out_dtype),
        )
    out = rec_stack(jnp.stack([er, ei]), e_mu, e_nu, ctx, method, out_dtype)
    return out[0], out[1]


# ================================================================ backends


def _composed_karatsuba(backend, arr, ari, brr, bri, ctx):
    """Residues of (CR', CI') via 3 residue products (paper eq. 10), composed
    from `backend.residue_matmul` — used by backends without a fused
    Karatsuba kernel (the jnp reference and the fp8 engine).  Every product
    returns canonical symmetric residues (|r| <= 127), so the host-side
    int32 combines stay exact."""
    asum = _sym_mod_stack(
        arr.astype(jnp.int32) + ari.astype(jnp.int32), ctx
    ).astype(jnp.int8)
    bsum = _sym_mod_stack(
        brr.astype(jnp.int32) + bri.astype(jnp.int32), ctx
    ).astype(jnp.int8)
    d = backend.residue_matmul(arr, brr, ctx).astype(jnp.int32)  # already mod p
    e = backend.residue_matmul(ari, bri, ctx).astype(jnp.int32)
    f = backend.residue_matmul(asum, bsum, ctx).astype(jnp.int32)
    er = _sym_mod_stack(d - e, ctx).astype(jnp.int8)
    ei = _sym_mod_stack(f - d - e, ctx).astype(jnp.int8)
    return er, ei


@dataclasses.dataclass(frozen=True)
class ReferenceBackend:
    """jnp reference data path (exact f64 host arithmetic; core/intmul.py)."""

    # launch capabilities consulted by the perfmodel-driven 'auto'
    # selections (make_plan): the reference path composes Karatsuba from 3
    # separate products and runs one launch per modulus
    fused_karatsuba = False
    modulus_batched = False
    uses_pallas = False

    def analyze(self, plan, shape=None):
        """Static-analysis suite certifying this engine (repro.analysis):
        overflow/exactness, collective safety, scan index width, and —
        given ``shape=(m, k, n)`` — the launch-count certificate (0 for
        the jnp reference path)."""
        from ..analysis import passes_for_backend

        return passes_for_backend(self, plan, shape)

    def cast(self, x, e, axis, ctx, n_limbs):
        """quantize by 2^e along `axis` and residue-decompose (steps IV/V-i/ii)."""
        xq = quantize(x.astype(jnp.float64), scaling.exp2i(e), axis)
        return residues_from_quantized(xq, ctx, n_limbs)

    def residue_matmul(self, ares, bres, ctx):
        """(N,m,k) x (N,k,n) -> (N,m,n) int8 residues of A'B' (steps V-iii/iv),
        K-chunked by the shared `chunked_residue_matmul`."""
        return chunked_residue_matmul(
            lambda a, b: _sym_mod_stack(int8_matmul(a, b), ctx).astype(jnp.int8),
            ares,
            bres,
            ctx,
        )

    def karatsuba(self, arr, ari, brr, bri, ctx):
        """Residues of (CR', CI') via 3 int8 GEMMs per modulus (paper eq. 10)."""
        return _composed_karatsuba(self, arr, ari, brr, bri, ctx)

    def reconstruct(self, e_res, e_mu, e_nu, ctx, method, out_dtype):
        """CRT reconstruction (steps V-v/vi) + exact inverse scaling.

        Exact only in IEEE f64.  A TPU emulates f64 with less precision and
        f32's exponent range, so there this path refuses to trace."""
        if jax.default_backend() == "tpu":
            raise NotImplementedError(
                "execution='reference' needs IEEE f64, which the TPU does not "
                "have; use execution='kernel' or 'fused' on a TPU"
            )
        hi, lo = crt.reconstruct(e_res, ctx, method)
        return crt.inverse_scale(hi, lo, e_mu, e_nu, out_dtype)


REFERENCE = ReferenceBackend()


@dataclasses.dataclass(frozen=True)
class Fp8Backend:
    """Residue backend running the modular products on the **FP8 (e4m3)
    engine** (`kernels/fp8_mod_gemm.py`, the arXiv:2603.10634 variant):
    residues split into balanced base-16 digits — exact in e4m3 — and each
    plane's product runs as three fp8 GEMMs accumulated in f32, rescaled
    into the residue ring per plane in the kernel epilogue.

    The first non-int8 engine through the residue-backend protocol: casts
    and Garner reconstruction are shared with the batched int8 kernel path
    (delegated to `KernelBackend`, so the plane layout and f32 quantization
    grade are identical), only the products run on the fp8 engine:
    `residue_matmul` as one batched digit-triple launch and `karatsuba` as
    the fused D/E/F digit kernel (one launch per K-chunk, declared via
    ``fused_karatsuba = True`` so the perfmodel-driven 'auto' selections
    charge the right launch count).  The digit split is exact,
    hence the whole pipeline is **bitwise identical** to
    ``execution="kernel"`` — what changes is the engine the MACs run on and
    therefore the `perfmodel` pricing (``engine = "fp8"``: 4 digit-MAC
    volumes at the e4m3 rate).

    Select via ``GemmPolicy(execution="fp8")``.  Off-TPU the kernels run in
    interpreted Pallas (bit-identical: the digits are exactly
    representable), so hosts without native fp8 matmul support fall back
    transparently.
    """

    interpret: bool | None = None

    # capability flags consulted by the perfmodel-driven 'auto' selections
    fused_karatsuba = True
    modulus_batched = True
    engine = "fp8"
    uses_pallas = True

    def analyze(self, plan, shape=None):
        """Static-analysis suite certifying the fp8 engine: the overflow
        pass uses `FP8_K_CHUNK_LIMIT` for the digit dots (see
        repro.analysis.passes_for_backend)."""
        from ..analysis import passes_for_backend

        return passes_for_backend(self, plan, shape)

    def _shared(self):
        # lazy import: core stays importable without the Pallas stack
        from ..kernels.ops import KernelBackend

        return KernelBackend(self.interpret)

    def cast(self, x, e, axis, ctx, n_limbs):
        return self._shared().cast(x, e, axis, ctx, n_limbs)

    def cast_stack(self, xs, e, axis, ctx, n_limbs):
        return self._shared().cast_stack(xs, e, axis, ctx, n_limbs)

    def reconstruct(self, e_res, e_mu, e_nu, ctx, method, out_dtype):
        return self._shared().reconstruct(e_res, e_mu, e_nu, ctx, method, out_dtype)

    def reconstruct_stack(self, e_res, e_mu, e_nu, ctx, method, out_dtype):
        return self._shared().reconstruct_stack(
            e_res, e_mu, e_nu, ctx, method, out_dtype
        )

    def residue_matmul(self, ares, bres, ctx):
        """One batched fp8 launch per K-chunk (chunked at the f32 digit
        accumulator's exactness bound, not the int8 engine's int32 bound)."""
        from ..kernels.fp8_mod_gemm import FP8_K_CHUNK_LIMIT, fp8_mod_gemm_batched

        return chunked_residue_matmul(
            lambda a, b, carry: fp8_mod_gemm_batched(
                a, b, moduli=ctx.moduli, carry=carry, interpret=self.interpret
            ),
            ares,
            bres,
            ctx,
            carry_epilogue=True,
            chunk_limit=FP8_K_CHUNK_LIMIT,
        )

    def karatsuba(self, arr, ari, brr, bri, ctx):
        """Fused fp8 Karatsuba: the D/E/F digit triples all run in ONE
        launch per K-chunk (`fp8_karatsuba_mod_gemm_batched`, 9 f32
        accumulators in VMEM) instead of 3 composed products with host
        combines — bitwise identical, chunked at the fp8 digit bound."""
        from ..kernels.fp8_mod_gemm import (
            FP8_K_CHUNK_LIMIT,
            fp8_karatsuba_mod_gemm_batched,
        )

        return chunked_residue_matmul(
            lambda a, b, carry: fp8_karatsuba_mod_gemm_batched(
                a[0], a[1], b[0], b[1],
                moduli=ctx.moduli, carry=carry, interpret=self.interpret,
            ),
            (arr, ari),
            (brr, bri),
            ctx,
            carry_epilogue=True,
            chunk_limit=FP8_K_CHUNK_LIMIT,
        )


# ------------------------------------------------- composed complex embeds


def _block_a(backend, arr, ari, brr, bri, ctx):
    """eq. (7): [[AR,-AI],[AI,AR]] @ [BR;BI] = [CR;CI] — one GEMM of (2m,2k,n)."""
    top = jnp.concatenate([arr, -ari], axis=-1)
    bot = jnp.concatenate([ari, arr], axis=-1)
    ahat = jnp.concatenate([top, bot], axis=-2)  # (N, 2m, 2k)
    bhat = jnp.concatenate([brr, bri], axis=-2)  # (N, 2k, n)
    chat = backend.residue_matmul(ahat, bhat, ctx)  # (N, 2m, n) int8 residues
    m = arr.shape[-2]
    return chat[:, :m, :], chat[:, m:, :]


def _block_b(backend, arr, ari, brr, bri, ctx):
    """eq. (8): [AI,AR] @ [[BR,-BI],[BI,BR]] = [CI,CR] — one GEMM of (m,2k,2n)."""
    ahat = jnp.concatenate([ari, arr], axis=-1)  # (N, m, 2k)
    left = jnp.concatenate([brr, bri], axis=-2)  # (N, 2k, n)
    right = jnp.concatenate([-bri, brr], axis=-2)
    bhat = jnp.concatenate([left, right], axis=-1)  # (N, 2k, 2n)
    chat = backend.residue_matmul(ahat, bhat, ctx)
    n = brr.shape[-1]
    return chat[:, :, n:], chat[:, :, :n]


@stage("product")
def _complex_product(backend, plan, arr, ari, brr, bri, ctx):
    if plan.formulation == "karatsuba":
        return backend.karatsuba(arr, ari, brr, bri, ctx)
    if plan.formulation == "block_a":
        return _block_a(backend, arr, ari, brr, bri, ctx)
    if plan.formulation == "block_b":
        return _block_b(backend, arr, ari, brr, bri, ctx)
    raise ValueError(f"unknown formulation {plan.formulation!r}")


# ================================================================ executor


def execute_plan(plan: EmulationPlan, a, b, backend=REFERENCE):
    """Run one 2D emulated GEMM per `plan`: C ~= A @ B, a: (m,k), b: (k,n)."""
    return (
        _execute_complex(plan, a, b, backend)
        if plan.is_complex
        else _execute_real(plan, a, b, backend)
    )


def _blocked_pipeline_real(plan, backend, ctx, e_mu, ares, e_nu, bres_slice, n):
    """The shared residue-GEMM -> reconstruct loop over output-column blocks.

    `bres_slice(sl)` yields the B-side residues for one block — freshly cast
    by the executor, or sliced out of a `PreparedOperand`.

    Backends exposing the `psum_partial`/`psum_combine` hooks (the sharded
    worker with a sharded residue axis) get the overlap-friendly two-phase
    structure: every block's residue product is issued before ANY partial is
    psummed, then ONE collective reduces the collected partial pytree, then
    the reconstructions run — so the collective is no longer serialized
    between consecutive blocks' products and XLA's async collectives can
    hide it behind them.  Bitwise identical (a pytree psum is the same
    per-leaf psum of exact f64 integer partials).
    """
    psum_partial = getattr(backend, "psum_partial", None)
    slices = list(plan.n_block_slices(n))
    if psum_partial is not None:
        partials = []
        for sl in slices:
            with stage("assemble"):
                bres = bres_slice(sl)
            with stage("product"):
                e_r = backend.residue_matmul(ares, bres, ctx)
            with stage("psum"):
                partials.append(psum_partial(e_r))
        with stage("psum"):
            planes = backend.psum_combine(partials)
        blocks = []
        for e_r, sl in zip(planes, slices):
            with stage("assemble"):
                e_nu_sl = e_nu[sl]
            with stage("garner"):
                blocks.append(backend.reconstruct_post(
                    e_r, e_mu, e_nu_sl, ctx, plan.method, plan.real_out_dtype
                ))
        return _concat_cols(blocks)
    blocks = []
    for sl in slices:
        with stage("assemble"):
            bres, e_nu_sl = bres_slice(sl), e_nu[sl]
        with stage("product"):
            e_r = backend.residue_matmul(ares, bres, ctx)
        with stage("garner"):
            blocks.append(backend.reconstruct(
                e_r, e_mu, e_nu_sl, ctx, plan.method, plan.real_out_dtype
            ))
    return _concat_cols(blocks)


def _blocked_pipeline_complex(
    plan, backend, ctx, e_mu, arr, ari, e_nu, bres_slice, n
):
    """Complex twin of `_blocked_pipeline_real`; `bres_slice(sl)` yields the
    (brr, bri) residue pair for one output-column block.  The two-phase
    psum hooks apply to the stacked CR/CI partials the same way.  Returns
    the planar (cr, ci) output."""
    rdt = plan.real_out_dtype
    psum_partial = getattr(backend, "psum_partial", None)
    slices = list(plan.n_block_slices(n))
    if psum_partial is not None:
        partials = []
        for sl in slices:
            with stage("assemble"):
                brr, bri = bres_slice(sl)
            er, ei = _complex_product(backend, plan, arr, ari, brr, bri, ctx)
            with stage("psum"):
                partials.append(psum_partial(jnp.stack([er, ei])))
        with stage("psum"):
            planes = backend.psum_combine(partials, stacked=True)
        blocks = []
        for full, sl in zip(planes, slices):
            with stage("assemble"):
                e_nu_sl = e_nu[sl]
            with stage("garner"):
                out = backend.reconstruct_post_stack(
                    full, e_mu, e_nu_sl, ctx, plan.method, rdt
                )
                blocks.append((out[0], out[1]))
        return _concat_planar(blocks)
    blocks = []
    for sl in slices:
        with stage("assemble"):
            (brr, bri), e_nu_sl = bres_slice(sl), e_nu[sl]
        er, ei = _complex_product(backend, plan, arr, ari, brr, bri, ctx)
        blocks.append(
            _reconstruct_pair(
                backend, er, ei, e_mu, e_nu_sl, ctx, plan.method, rdt
            )
        )
    return _concat_planar(blocks)


@stage("assemble")
def _concat_cols(blocks):
    """Output-column blocks -> one output."""
    return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=1)


@stage("assemble")
def _concat_planar(blocks):
    """[(cr, ci), ...] output-column blocks -> one planar (cr, ci) pair."""
    if len(blocks) == 1:
        return blocks[0]
    return tuple(jnp.concatenate(part, axis=1) for part in zip(*blocks))


@stage("assemble")
def _planar(x):
    """A complex operand's (real, imag) parts."""
    return jnp.real(x), jnp.imag(x)


@stage("assemble")
def _to_complex(planar):
    """A planar (cr, ci) output as one complex array."""
    return jax.lax.complex(*planar)


# ------------------------------------------------------- fused megakernel


def _fused_pipeline_real(plan, backend, ctx, e_mu, a, e_nu, b_slice,
                         b_res_slice, n):
    """Real pipeline on a megakernel backend: ONE `fused_gemm` launch per
    output-column block (cast prologue + products + Garner epilogue all
    in-kernel).  `b_slice(sl)` yields the raw B block, or `b_res_slice(sl)`
    the pre-cast (N, k, n_blk) planes of a prepared operand."""
    blocks = []
    for sl in plan.n_block_slices(n):
        if b_res_slice is not None:
            with stage("assemble"):
                b_res, e_nu_sl = b_res_slice(sl), e_nu[sl]
            with stage("fused"):
                out = backend.fused_gemm(
                    a, None, e_mu, e_nu_sl, ctx, plan.n_limbs,
                    plan.real_out_dtype, b_res=b_res,
                )
        else:
            with stage("assemble"):
                b_blk, e_nu_sl = b_slice(sl), e_nu[sl]
            with stage("fused"):
                out = backend.fused_gemm(
                    a, b_blk, e_mu, e_nu_sl, ctx, plan.n_limbs,
                    plan.real_out_dtype,
                )
        blocks.append(out)
    return _concat_cols(blocks)


@stage("fused")
def _fused_complex_block(
    backend, plan, ctx, e_mu, ar, ai, e_nu_sl, b_blk, b_res_blk, nl, rdt
):
    """One output-column block of the fused complex pipeline -> (cr, ci);
    the block embeddings and the output's halves run in the fused stage.

    'karatsuba' runs the fused complex megakernel directly.  The block
    embeddings (paper eqs. 7/8) embed the RAW operands (or, prepared, the
    int8 residue planes) and run the real megakernel once: the residue cast
    commutes bitwise with negation (trunc and round are symmetric), so
    cast(-AI) equals the composed path's negated int8 planes exactly.
    """
    if plan.formulation == "karatsuba":
        if b_res_blk is not None:
            return backend.fused_karatsuba_gemm(
                ar, ai, None, None, e_mu, e_nu_sl, ctx, nl, rdt,
                b_res=b_res_blk,
            )
        return backend.fused_karatsuba_gemm(
            ar, ai, b_blk[0], b_blk[1], e_mu, e_nu_sl, ctx, nl, rdt
        )
    if plan.formulation == "block_a":
        # eq. (7): [[AR,-AI],[AI,AR]] @ [BR;BI] = [CR;CI]
        ahat = jnp.concatenate(
            [
                jnp.concatenate([ar, -ai], axis=-1),
                jnp.concatenate([ai, ar], axis=-1),
            ],
            axis=-2,
        )
        ehat = jnp.concatenate([e_mu, e_mu])
        if b_res_blk is not None:
            chat = backend.fused_gemm(
                ahat, None, ehat, e_nu_sl, ctx, nl, rdt,
                b_res=jnp.concatenate(b_res_blk, axis=-2),
            )
        else:
            bhat = jnp.concatenate(b_blk, axis=-2)
            chat = backend.fused_gemm(ahat, bhat, ehat, e_nu_sl, ctx, nl, rdt)
        m = ar.shape[-2]
        return chat[..., :m, :], chat[..., m:, :]
    if plan.formulation == "block_b":
        # eq. (8): [AI,AR] @ [[BR,-BI],[BI,BR]] = [CI,CR]
        ahat = jnp.concatenate([ai, ar], axis=-1)
        ehat_nu = jnp.concatenate([e_nu_sl, e_nu_sl])
        if b_res_blk is not None:
            brr, bri = b_res_blk
            bhat = jnp.concatenate(
                [
                    jnp.concatenate([brr, bri], axis=-2),
                    jnp.concatenate([-bri, brr], axis=-2),
                ],
                axis=-1,
            )
            chat = backend.fused_gemm(
                ahat, None, e_mu, ehat_nu, ctx, nl, rdt, b_res=bhat
            )
        else:
            br, bi = b_blk
            bhat = jnp.concatenate(
                [
                    jnp.concatenate([br, bi], axis=-2),
                    jnp.concatenate([-bi, br], axis=-2),
                ],
                axis=-1,
            )
            chat = backend.fused_gemm(ahat, bhat, e_mu, ehat_nu, ctx, nl, rdt)
        n = chat.shape[-1] // 2
        return chat[..., :, n:], chat[..., :, :n]
    raise ValueError(f"unknown formulation {plan.formulation!r}")


def _fused_pipeline_complex(
    plan, backend, ctx, e_mu, ar, ai, e_nu, b_slice, b_res_slice, n
):
    """Complex pipeline on a megakernel backend: one launch per block.
    Returns the planar (cr, ci) output."""
    nl = plan.n_limbs
    rdt = plan.real_out_dtype
    blocks = []
    for sl in plan.n_block_slices(n):
        with stage("assemble"):
            b_blk = None if b_res_slice is not None else b_slice(sl)
            b_res_blk = b_res_slice(sl) if b_res_slice is not None else None
            e_nu_sl = e_nu[sl]
        blocks.append(
            _fused_complex_block(
                backend, plan, ctx, e_mu, ar, ai, e_nu_sl, b_blk, b_res_blk,
                nl, rdt,
            )
        )
    return _concat_planar(blocks)


def _accu_combines(backend):
    """Sharded backends expose `accu_row_combine` / `accu_col_combine`
    (lax.pmax over the n-/m-sharded mesh axes) so the accurate-mode bound
    maxima cover the whole output row/column, not just this shard's tile."""
    return (
        getattr(backend, "accu_row_combine", None),
        getattr(backend, "accu_col_combine", None),
    )


def _execute_real(plan, a, b, backend):
    ctx = plan.ctx
    with stage("scale"):
        if plan.mode == "fast":
            e_mu, e_nu = scaling.scale_fast_real(a, b, ctx)
        else:
            rc, cc = _accu_combines(backend)
            e_mu, e_nu = scaling.scale_accurate_real(a, b, ctx, rc, cc)
    nl = plan.n_limbs
    if getattr(backend, "megakernel", False):
        # fast AND accu mode: the scaling pass above is pallas-free, so the
        # whole emulated GEMM is the megakernel's single launch per block
        return _fused_pipeline_real(
            plan, backend, ctx, e_mu, a, e_nu,
            lambda sl: b[:, sl], None, b.shape[1],
        )
    ares = _cast(backend, a, e_mu, 0, ctx, nl)
    return _blocked_pipeline_real(
        plan, backend, ctx, e_mu, ares, e_nu,
        lambda sl: _cast(backend, b[:, sl], e_nu[sl], 1, ctx, nl),
        b.shape[1],
    )


def _execute_complex(plan, a, b, backend):
    return _to_complex(
        execute_plan_planar(plan, *_planar(a), *_planar(b), backend)
    )


def execute_plan_planar(plan: EmulationPlan, ar, ai, br, bi, backend=REFERENCE):
    """One 2D complex emulated GEMM on planar operands: (ar + i ai) @
    (br + i bi) -> the planar (cr, ci) output.  No complex array is formed,
    so a c128 product never puts a c128 array on the device."""
    ctx = plan.ctx
    with stage("scale"):
        if plan.mode == "fast":
            e_mu, e_nu = scaling.scale_fast_complex(ar, ai, br, bi, ctx)
        else:
            rc, cc = _accu_combines(backend)
            e_mu, e_nu = scaling.scale_accurate_complex(
                ar, ai, br, bi, ctx, rc, cc
            )
    nl = plan.n_limbs
    if getattr(backend, "megakernel", False):
        return _fused_pipeline_complex(
            plan, backend, ctx, e_mu, ar, ai, e_nu,
            lambda sl: (br[:, sl], bi[:, sl]), None, br.shape[1],
        )
    arr, ari = _cast_pair(backend, ar, ai, e_mu, 0, ctx, nl)
    return _blocked_pipeline_complex(
        plan, backend, ctx, e_mu, arr, ari, e_nu,
        lambda sl: _cast_pair(backend, br[:, sl], bi[:, sl], e_nu[sl], 1, ctx, nl),
        br.shape[1],
    )


@functools.partial(
    jnp.vectorize, excluded=(2, 3), signature="(m,k),(k,n)->(m,n)"
)
def _run_plan_2d(a, b, plan, backend):
    return execute_plan(plan, a, b, backend)


@functools.partial(
    jnp.vectorize, excluded=(4, 5),
    signature="(m,k),(m,k),(k,n),(k,n)->(m,n),(m,n)",
)
def _run_plan_planar_2d(ar, ai, br, bi, plan, backend):
    return execute_plan_planar(plan, ar, ai, br, bi, backend)


def run_plan_planar(plan: EmulationPlan, ar, ai, br, bi, backend=REFERENCE):
    """`run_plan` for a complex plan on planar operands -> (cr, ci)."""
    runner = getattr(backend, "run_plan_planar", None)
    if runner is not None:
        return runner(plan, ar, ai, br, bi)
    return _run_plan_planar_2d(ar, ai, br, bi, plan, backend)


def run_plan(plan: EmulationPlan, a, b, backend=REFERENCE):
    """Execute `plan` on (..., m, k) x (..., k, n), batched over leading dims.

    A backend may take over the whole execution by providing `run_plan`
    (the sharded backend does: it shard_maps `execute_plan` over the mesh
    with a per-shard worker, so batching/vectorize does not apply there).
    """
    runner = getattr(backend, "run_plan", None)
    if runner is not None:
        return runner(plan, a, b)
    return _run_plan_2d(a, b, plan, backend)


# ====================================================== prepared operands


class PreparedOperand:
    """Beyond-paper optimization: one-time residue-cast of a reused operand.

    In iterative solvers, repeated applications (C_i = A @ B_i with a fixed
    A) and weight-stationary serving (Y = X_i @ W), step 1 of the scheme
    (scaling + truncation + N residue planes of the fixed operand) can be
    computed once and amortized: the paper's step-1 memory term
    ((3N + 32 + c) k (m+n) / b) loses the prepared side's contribution
    entirely on every call after the first.  Scaling uses the fast
    (Cauchy-Schwarz) per-row/column bound, which is independent of the other
    operand — so `gemm_prepared` is bit-identical to the direct fast-mode
    pipeline.

    Accurate mode (``keep_raw=True``, done by `prepare_weights` for accu
    policies): the operand additionally stores its per-row/column 7-bit
    bound matrix (`bound`/`e_bound`, paper eqs. 13-14) — the only
    accurate-mode quantity that depends on one operand alone — plus the raw
    operand.  The residue planes themselves CANNOT be pre-cast for accu
    calls: the accurate exponents couple both operands through the
    auxiliary product `cbar = abar @ bbar`, so the truncation position of
    the prepared side depends on the streaming operand.  An accu-mode
    `gemm_prepared` therefore reuses the stored bound (bitwise what the
    direct pipeline recomputes) and re-casts from the raw operand per
    call.  Fast-mode operands skip both extras, staying exactly
    residue-planes-sized (and checkpoint-compatible with older saves).

    Supports real and complex operands, either side of the product
    (`side='left'` prepares A row-wise; `side='right'` prepares B
    column-wise) and leading batch dims (e.g. scan-stacked layer weights:
    a (L, k, n) weight yields residues (L, N, k, n), sliced per layer by
    `lax.scan` like any other parameter leaf).  Instances are registered as
    jax pytrees so they can live inside jitted parameter trees.

    `backend` selects who runs the residue cast (default: the jnp reference
    backend).  Preparing with the execution backend that will consume the
    residues keeps prepared and unprepared runs bit-identical on that
    backend — e.g. the Pallas kernel cast quantizes through f32, so a
    kernel-path server must prepare with the kernel backend (the policy
    layer's `prepare_weights` does this automatically).

    Example — prepare a weight once, multiply many times::

        >>> import jax.numpy as jnp
        >>> from repro.core import PreparedOperand, gemm_prepared
        >>> w = jnp.asarray([[1.0, 2.0], [4.0, 0.5], [8.0, 1.0]])  # (k, n)
        >>> prep = PreparedOperand(w, n_moduli=6, side="right")
        >>> prep.res.shape                    # N int8 residue planes of w
        (6, 3, 2)
        >>> x = jnp.eye(3, dtype=jnp.float64) * 2.0
        >>> y = gemm_prepared(prep, x)        # C ~= x @ w, w-side amortized
        >>> bool(jnp.all(y == 2.0 * w))       # exact: power-of-two operands
        True
    """

    def __init__(
        self, x, n_moduli: int | None = None, side: str = "left", backend=None,
        keep_raw: bool = False,
    ):
        if side not in ("left", "right"):
            raise ValueError(side)
        if backend is None:
            backend = REFERENCE
        dt = jnp.dtype(x.dtype)
        if n_moduli is None:
            from .plan import default_n_moduli

            n_moduli = default_n_moduli(dt, "fast")
        n_moduli = int(n_moduli)
        ctx = make_crt_context(n_moduli)
        nl = n_limbs_for_ctx(ctx)
        is_complex = jnp.issubdtype(dt, jnp.complexfloating)
        axis = 0 if side == "left" else 1
        evec = "(m)" if side == "left" else "(k)"

        # the two preparation flavours store disjoint things, because the
        # executions read disjoint things: fast-mode calls consume the
        # pre-cast residue planes (the amortization), accu-mode calls
        # consume the bound + raw operand and re-cast at the coupled
        # exponents.  Skipping the unused half keeps fast-mode operands
        # exactly residue-planes-sized (bit-compatible with older
        # checkpoints) and accu preparation free of a dead residue cast.
        e_scale = None
        res: list = []
        if not keep_raw:
            sig = f"(m,k)->{evec},(l,m,k)"
            if is_complex:

                @functools.partial(
                    jnp.vectorize, signature=f"(m,k)->{evec},(l,m,k),(l,m,k)"
                )
                def _prep(x2):
                    xr, xi = _planar(x2)
                    e = _solo_scale_complex(xr, xi, ctx, side)
                    rr, ri = _cast_pair(backend, xr, xi, e, axis, ctx, nl)
                    return e, rr, ri

                e_scale, *res = _prep(x)
            else:

                @functools.partial(jnp.vectorize, signature=sig)
                def _prep(x2):
                    e = _solo_scale_real(x2, ctx, side)
                    return e, _cast(backend, x2, e, axis, ctx, nl)

                e_scale, *res = _prep(x)

        bound: tuple = ()
        e_bound = None
        if keep_raw:
            if is_complex:

                @stage("scale")
                @functools.partial(
                    jnp.vectorize, signature=f"(m,k)->(m,k),(m,k),{evec}"
                )
                def _bound(x2):
                    bars, e_bar, _ = scaling.accu_bound_complex(
                        jnp.real(x2), jnp.imag(x2), side
                    )
                    return bars[0], bars[1], e_bar

                *bound, e_bound = _bound(x)
            else:

                @stage("scale")
                @functools.partial(
                    jnp.vectorize, signature=f"(m,k)->(m,k),{evec}"
                )
                def _bound(x2):
                    bar, e_bar, _ = scaling.accu_bound_real(x2, side)
                    return bar, e_bar

                *bound, e_bound = _bound(x)

        self.side = side
        self.n_moduli = n_moduli
        self.n_limbs = nl
        self.dtype = dt.name
        self.e_scale = e_scale
        self.residues = tuple(res)
        self.bound = tuple(bound)
        self.e_bound = e_bound
        self.raw = jnp.asarray(x) if keep_raw else None

    # residues of the real part (kept under the historical name)
    @property
    def res(self):
        return self.residues[0]

    @property
    def is_complex(self) -> bool:
        return jnp.issubdtype(jnp.dtype(self.dtype), jnp.complexfloating)

    @property
    def mode(self) -> str:
        """The scaling mode this operand was prepared for, recorded by what
        it stores: fast preparation stores residue planes, accu preparation
        stores the 7-bit bound + raw operand (`keep_raw`).  Derived rather
        than carried in the pytree aux, so older fast-mode checkpoints
        round-trip unchanged.  The policy layer checks this against the
        (possibly adaptively resolved) calling policy and raises instead of
        returning silently wrong answers."""
        return "fast" if self.residues else "accu"

    @property
    def ctx(self) -> CRTContext:
        return make_crt_context(self.n_moduli)

    @property
    def batch_ndim(self) -> int:
        """Leading batch dims of the prepared operand (0 = a plain matrix)."""
        if self.residues:
            return self.residues[0].ndim - 3  # (.., L, m, k) planes
        return self.bound[0].ndim - 2  # (.., m, k) bound matrix

    @property
    def operand_shape(self) -> tuple[int, int]:
        """Logical (rows, cols) of the prepared operand (per batch element)."""
        arrs = self.residues if self.residues else self.bound
        return arrs[0].shape[-2:]

    def __repr__(self):
        return (
            f"PreparedOperand(side={self.side!r}, dtype={self.dtype}, "
            f"mode={self.mode!r}, n_moduli={self.n_moduli}, "
            f"shape={self.operand_shape})"
        )


def _prepared_flatten(p: PreparedOperand):
    children = (p.e_scale, p.residues, p.bound, p.e_bound, p.raw)
    return children, (p.side, p.n_moduli, p.n_limbs, p.dtype)


def _prepared_unflatten(aux, children):
    p = object.__new__(PreparedOperand)
    p.side, p.n_moduli, p.n_limbs, p.dtype = aux
    p.e_scale, res, bound, p.e_bound, p.raw = children
    p.residues = tuple(res)
    p.bound = tuple(bound)
    return p


jax.tree_util.register_pytree_node(
    PreparedOperand, _prepared_flatten, _prepared_unflatten
)


@stage("scale")
def _solo_scale_real(x, ctx, side):
    """Fast-mode exponent of one operand alone (dummy other operand)."""
    if side == "left":
        e, _ = scaling.scale_fast_real(x, jnp.zeros((x.shape[1], 1)), ctx)
    else:
        _, e = scaling.scale_fast_real(jnp.zeros((1, x.shape[0])), x, ctx)
    return e


@stage("scale")
def _solo_scale_complex(xr, xi, ctx, side):
    if side == "left":
        z = jnp.zeros((xr.shape[1], 1))
        e, _ = scaling.scale_fast_complex(xr, xi, z, z, ctx)
    else:
        z = jnp.zeros((1, xr.shape[0]))
        _, e = scaling.scale_fast_complex(z, z, xr, xi, ctx)
    return e


def _gemm_prepared_accu(prep, x, plan, backend):
    """Accurate-mode prepared product: reuse the stored 7-bit bound, re-cast
    from the raw operand at the call-time coupled exponents.

    The accurate exponents couple both operands (`cbar = abar @ bbar`), so
    the only amortizable step-1 work is the prepared side's bound matrix —
    this path computes exactly the operations of `_execute_real` /
    `_execute_complex` in the same order, sourcing (bar, e_bar) from the
    preparation, and is therefore bitwise identical to the unprepared accu
    run on every backend.
    """
    if prep.raw is None:
        raise ValueError(
            "accu-mode prepared matmuls re-cast from the raw operand (the "
            "accurate exponents couple both operands); prepare with "
            "keep_raw=True / prepare_weights(accu policy)"
        )
    ctx = prep.ctx
    nl = prep.n_limbs
    other = "left" if prep.side == "right" else "right"

    if prep.is_complex:
        xr, xi = _planar(x)
        with stage("scale"):
            xbar, e_xbar, x_nz = scaling.accu_bound_complex(xr, xi, other)
            pbar, e_pbar = prep.bound, prep.e_bound
            p_nz = jnp.max(
                jnp.maximum(*[b.astype(jnp.int32) for b in pbar]),
                axis=1 if prep.side == "left" else 0,
            ) > 0
        wr, wi = _planar(prep.raw)
        with stage("scale"):
            if prep.side == "left":
                cmax = scaling.accu_cbar_complex(pbar, xbar)
                e_mu, e_nu = scaling.accu_exponents(
                    cmax, e_pbar, e_xbar, p_nz, x_nz, ctx
                )
                ar_, ai_ = wr, wi
                br_, bi_ = xr, xi
            else:
                cmax = scaling.accu_cbar_complex(xbar, pbar)
                e_mu, e_nu = scaling.accu_exponents(
                    cmax, e_xbar, e_pbar, x_nz, p_nz, ctx
                )
                ar_, ai_ = xr, xi
                br_, bi_ = wr, wi
        if getattr(backend, "megakernel", False):
            # accu re-casts from raw anyway, so the fused prologue applies
            return _to_complex(_fused_pipeline_complex(
                plan, backend, ctx, e_mu, ar_, ai_, e_nu,
                lambda sl: (br_[:, sl], bi_[:, sl]), None, br_.shape[1],
            ))
        arr, ari = _cast_pair(backend, ar_, ai_, e_mu, 0, ctx, nl)
        return _to_complex(_blocked_pipeline_complex(
            plan, backend, ctx, e_mu, arr, ari, e_nu,
            lambda sl: _cast_pair(
                backend, br_[:, sl], bi_[:, sl], e_nu[sl], 1, ctx, nl
            ),
            br_.shape[1],
        ))

    with stage("scale"):
        xbar, e_xbar, x_nz = scaling.accu_bound_real(x, other)
        pbar, e_pbar = prep.bound[0], prep.e_bound
        p_nz = jnp.max(
            pbar.astype(jnp.int32), axis=1 if prep.side == "left" else 0
        ) > 0
        if prep.side == "left":
            cbar = int8_matmul(pbar, xbar)
            e_mu, e_nu = scaling.accu_exponents(
                cbar, e_pbar, e_xbar, p_nz, x_nz, ctx
            )
            a_, b_ = prep.raw, x
        else:
            cbar = int8_matmul(xbar, pbar)
            e_mu, e_nu = scaling.accu_exponents(
                cbar, e_xbar, e_pbar, x_nz, p_nz, ctx
            )
            a_, b_ = x, prep.raw
    if getattr(backend, "megakernel", False):
        return _fused_pipeline_real(
            plan, backend, ctx, e_mu, a_, e_nu,
            lambda sl: b_[:, sl], None, b_.shape[1],
        )
    ares = _cast(backend, a_, e_mu, 0, ctx, nl)
    return _blocked_pipeline_real(
        plan, backend, ctx, e_mu, ares, e_nu,
        lambda sl: _cast(backend, b_[:, sl], e_nu[sl], 1, ctx, nl),
        b_.shape[1],
    )


def gemm_prepared(
    prep: PreparedOperand,
    x: jnp.ndarray,
    method: str = "paper",
    formulation: str = "karatsuba",
    out_dtype=None,
    n_block=None,
    backend=REFERENCE,
    mode: str = "fast",
) -> jnp.ndarray:
    """Emulated product with one prepared side.

    side='left':  C ~= prep @ x   (x is B, cast per call)
    side='right': C ~= x @ prep   (x is A, cast per call)

    `formulation` (complex operands) accepts 'auto' and `n_block` accepts
    int | None | 'auto', resolved exactly as in the direct pipeline.

    Bit-identical to the direct pipeline in both modes.  mode='fast': the
    fast scaling bound of each operand is independent of the other, so the
    prepared exponents and residues match what the direct run computes and
    the prepared side's cast is skipped entirely.  mode='accu': the stored
    per-row/column bound replaces its recomputation, and the residue casts
    run per call at the coupled exponents (`_gemm_prepared_accu`).
    """
    ctx = prep.ctx
    if prep.batch_ndim != 0:
        raise ValueError(
            "gemm_prepared expects an unbatched (2D) prepared operand; "
            f"got a {prep.batch_ndim}-batched preparation of "
            f"shape {prep.operand_shape}"
        )
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    if prep.side == "left":
        m, k = prep.operand_shape
        n = x.shape[1]
    else:
        k, n = prep.operand_shape
        m = x.shape[0]
    plan = make_plan(
        prep.dtype,
        n_moduli=prep.n_moduli,
        mode=mode,
        method=method,
        formulation=formulation if prep.is_complex else None,
        out_dtype=out_dtype,
        n_block=n_block,
        shape=(m, k, n),
        # the 'auto' selections must charge launches and engine ops exactly
        # as the executing backend issues them, or a prepared run could pick
        # a different formulation than the unprepared run it must bit-match
        fused_karatsuba=getattr(backend, "fused_karatsuba", False),
        modulus_batched=getattr(backend, "modulus_batched", False),
        engine=getattr(backend, "engine", "int8"),
        megakernel=getattr(backend, "megakernel", False),
    )
    nl = prep.n_limbs
    other_side = "left" if prep.side == "right" else "right"

    if mode == "accu":
        return _gemm_prepared_accu(prep, x, plan, backend)
    if mode != "fast":
        raise ValueError(f"unknown mode {mode!r}")
    if not prep.residues:
        raise ValueError(
            "this operand was prepared for accu mode (bound + raw only); "
            "fast-mode calls consume pre-cast residue planes — re-prepare "
            "with prepare_weights(fast policy)"
        )

    # the fused megakernel casts the streaming side in its prologue and
    # consumes the prepared side's planes directly — one launch per block.
    # A LEFT-prepared fast operand stores planes but no raw matrix, and the
    # megakernel prologue needs the raw A tile, so side='left' falls through
    # to the composed kernel path the megakernel backend inherits.
    fused = getattr(backend, "megakernel", False) and prep.side == "right"

    if prep.is_complex:
        xr, xi = _planar(x)
        e_other = _solo_scale_complex(xr, xi, ctx, other_side)
        if prep.side == "left":
            e_mu, e_nu = prep.e_scale, e_other
            arr, ari = prep.residues
            bres_slice = lambda sl: _cast_pair(  # noqa: E731
                backend, xr[:, sl], xi[:, sl], e_nu[sl], 1, ctx, nl
            )
        else:
            e_mu, e_nu = e_other, prep.e_scale
            if fused:
                return _to_complex(_fused_pipeline_complex(
                    plan, backend, ctx, e_mu, xr, xi, e_nu, None,
                    lambda sl: tuple(r[..., sl] for r in prep.residues), n,
                ))
            arr, ari = _cast_pair(backend, xr, xi, e_mu, 0, ctx, nl)
            bres_slice = lambda sl: tuple(  # noqa: E731
                r[..., sl] for r in prep.residues
            )
        return _to_complex(_blocked_pipeline_complex(
            plan, backend, ctx, e_mu, arr, ari, e_nu, bres_slice, n
        ))

    e_other = _solo_scale_real(x, ctx, other_side)
    if prep.side == "left":
        e_mu, e_nu, ares = prep.e_scale, e_other, prep.res
        bres_slice = lambda sl: _cast(  # noqa: E731
            backend, x[:, sl], e_nu[sl], 1, ctx, nl
        )
    else:
        e_mu, e_nu = e_other, prep.e_scale
        if fused:
            return _fused_pipeline_real(
                plan, backend, ctx, e_mu, x, e_nu, None,
                lambda sl: prep.res[..., sl], n,
            )
        ares = _cast(backend, x, e_mu, 0, ctx, nl)
        bres_slice = lambda sl: prep.res[..., sl]  # noqa: E731
    return _blocked_pipeline_real(
        plan, backend, ctx, e_mu, ares, e_nu, bres_slice, n
    )
