"""Block-size autotuner for the batched/fused Pallas GEMM kernels.

Times the *public kernel entry points* — the exact functions the residue
backends call — over a small aligned candidate grid of (bm, bn, bk) per
(kernel family, dtype class, shape bucket), and returns the winners in the
`Calibration.blocks` format (`cache.block_key` -> (bm, bn, bk)).

Three facts make this safe and cheap:

* every kernel pads-and-slices (`kernels/common.block_and_padded`), so the
  block shape can never change numerics — the autotuner only ever trades
  speed, which is why the winners need no accuracy re-validation;
* each slot's default is always in its candidate set — the static
  ``(256, 256, 512)``, and for the ("kernel", "complex") slot also the
  tile `kernels.common.product_tile` picks for the shape — so a tuned
  configuration is never *measured* slower than the default at tune time;
  throughput can only hold or improve;
* candidates are MXU-aligned multiples (bm/bn of 128, bk of at least 128),
  and they flow through the same `block_and_padded` selection the defaults
  do, so a tuned block larger than a dim still shrinks exactly like the
  default would.

Smoke mode (CI) shrinks the shapes and the candidate grid so the whole
sweep stays in interpret-mode-on-CPU budget; a full run on real hardware
sweeps a wider grid per bucket.
"""
from __future__ import annotations

import time

from .cache import block_key

#: (bm, bn, bk) grids; the static kernel default leads both lists
DEFAULT_BLOCKS = (256, 256, 512)
_CANDIDATES_FULL = (
    DEFAULT_BLOCKS,
    (128, 128, 512),
    (128, 256, 512),
    (256, 128, 512),
    (256, 256, 256),
    (512, 512, 512),
    (256, 256, 1024),
)
_CANDIDATES_SMOKE = (
    DEFAULT_BLOCKS,
    (128, 128, 256),
)

#: tuned GEMM shapes: one bucket-representative per mode.  Smoke covers the
#: floor bucket (m128n128k128 — where the CI bench's tiny shapes land) plus
#: one multi-tile bucket so the sweep exercises a real grid.
_SHAPES_FULL = ((512, 512, 1024), (2048, 2048, 2048))
_SHAPES_SMOKE = ((128, 128, 128), (256, 128, 256))

_N_MODULI_SMOKE = 4
_N_MODULI_FULL = 8


def _median_time_s(fn, iters: int) -> float:
    import jax
    import numpy as np

    jax.block_until_ready(fn())  # compile + warm
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _make_entry(family: str, dclass: str, m: int, n: int, k: int,
                n_moduli: int):
    """A closure (bm, bn, bk) -> jitted-call thunk for one kernel slot."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..core.moduli import make_crt_context
    from ..core.plan import n_limbs_for_ctx
    from ..kernels.fp8_mod_gemm import (
        fp8_karatsuba_mod_gemm_batched,
        fp8_mod_gemm_batched,
    )
    from ..kernels.int8_mod_gemm import fused_mod_gemm, int8_mod_gemm_batched
    from ..kernels.karatsuba_fused import (
        fused_karatsuba_mod_gemm,
        karatsuba_mod_gemm_batched,
    )

    ctx = make_crt_context(n_moduli)
    rng = np.random.default_rng(0)

    def _planes(shape):
        return jnp.asarray(rng.integers(-60, 61, shape, dtype=np.int8))

    if family in ("kernel", "fp8"):
        if dclass == "real":
            kern = (fp8_mod_gemm_batched if family == "fp8"
                    else int8_mod_gemm_batched)
            a, b = _planes((n_moduli, m, k)), _planes((n_moduli, k, n))

            def entry(bm, bn, bk):
                f = functools.partial(
                    kern, a, b, moduli=ctx.moduli, bm=bm, bn=bn, bk=bk
                )
                return lambda: f()
        else:
            kern = (fp8_karatsuba_mod_gemm_batched if family == "fp8"
                    else karatsuba_mod_gemm_batched)
            ops = (_planes((n_moduli, m, k)), _planes((n_moduli, m, k)),
                   _planes((n_moduli, k, n)), _planes((n_moduli, k, n)))

            def entry(bm, bn, bk):
                f = functools.partial(
                    kern, *ops, moduli=ctx.moduli, bm=bm, bn=bn, bk=bk
                )
                return lambda: f()
        return entry

    if family != "fused":
        raise ValueError(f"unknown kernel family {family!r}")
    n_limbs = n_limbs_for_ctx(ctx)
    e_mu = jnp.zeros((m,), jnp.int32)
    e_nu = jnp.zeros((n,), jnp.int32)

    def _mant(shape):
        return jnp.asarray(rng.integers(-500, 501, shape), jnp.float32)

    if dclass == "real":
        a, b = _mant((m, k)), _mant((k, n))

        def entry(bm, bn, bk):
            def call():
                return fused_mod_gemm(
                    a, b, e_mu, e_nu, ctx, n_limbs=n_limbs,
                    bm=bm, bn=bn, bk=bk,
                )
            return call
    else:
        ar, ai = _mant((m, k)), _mant((m, k))
        br, bi = _mant((k, n)), _mant((k, n))

        def entry(bm, bn, bk):
            def call():
                return fused_karatsuba_mod_gemm(
                    ar, ai, br, bi, e_mu, e_nu, ctx, n_limbs=n_limbs,
                    bm=bm, bn=bn, bk=bk,
                )
            return call
    return entry


def autotune_blocks(
    smoke: bool = False,
    *,
    families: tuple[str, ...] = ("kernel", "fused", "fp8"),
    dclasses: tuple[str, ...] = ("real", "complex"),
    shapes: tuple[tuple[int, int, int], ...] | None = None,
    candidates: tuple[tuple[int, int, int], ...] | None = None,
    iters: int = 2,
    verbose: bool = False,
) -> dict:
    """Sweep the candidate grid; returns {block_key: (bm, bn, bk)} winners.

    The static default triple is force-included in `candidates`, and the
    ("kernel", "complex") slots' default tile in theirs, so the recorded
    winner for every slot is measured at least as fast as the default at
    tune time.
    """
    from ..kernels.common import product_tile

    shapes = shapes or (_SHAPES_SMOKE if smoke else _SHAPES_FULL)
    candidates = tuple(candidates or
                       (_CANDIDATES_SMOKE if smoke else _CANDIDATES_FULL))
    if DEFAULT_BLOCKS not in candidates:
        candidates = (DEFAULT_BLOCKS,) + candidates
    n_moduli = _N_MODULI_SMOKE if smoke else _N_MODULI_FULL
    winners: dict = {}
    for family in families:
        for dclass in dclasses:
            for m, n, k in shapes:
                entry = _make_entry(family, dclass, m, n, k, n_moduli)
                slot = candidates
                if (family, dclass) == ("kernel", "complex"):
                    # this slot only: the fused kernels overflow VMEM at it
                    tile = product_tile(m, n, k)
                    if tile not in candidates:
                        slot = (tile,) + candidates
                best, best_t = None, float("inf")
                for bm, bn, bk in slot:
                    t = _median_time_s(entry(bm, bn, bk), iters)
                    if verbose:
                        print(
                            f"  tune {family}/{dclass} {m}x{n}x{k} "
                            f"({bm},{bn},{bk}): {t * 1e6:.0f} us"
                        )
                    if t < best_t:
                        best, best_t = (bm, bn, bk), t
                winners[block_key(family, dclass, m, n, k)] = best
    return winners
