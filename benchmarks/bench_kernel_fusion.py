"""SPerf hillclimb 3 (kernel level): fused-Karatsuba vs separate-GEMM
modular complex multiply — HLO bytes-accessed comparison — plus the
modulus-batched launch-count check.

The paper launches D/E/F as separate int8 GEMM kernels with int32
intermediates in HBM; our Pallas kernel (kernels/karatsuba_fused.py) forms
(AR+AI) mod p in VMEM and writes the CR/CI residues directly, and the
batched grid runs all N moduli in ONE `pallas_call`.  On CPU we can't time
the TPU kernel, but two structural properties are checkable anywhere:

  * the *bytes* story — HLO bytes of both pipelines at the same shape and
    the exact per-modulus HBM traffic model;
  * the *launch* story — `pallas_call` counts of the full batched pipeline
    traced to jaxpr must match `perfmodel.kernel_launch_count` (2 casts +
    1 product + 1 reconstruction at any N).  A mismatch exits non-zero, so
    the CI smoke run (`--smoke`, tiny shapes, interpret mode) fails on
    launch-count regressions instead of waiting for hardware.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import GemmPolicy, perfmodel
from repro.core.moduli import make_crt_context
from repro.kernels import count_pallas_launches, karatsuba_mod_gemm
from repro.kernels import ref as kref
from repro import linalg

from .common import emit


def analytic(m, n, k):
    """Bytes/modulus moved to/from HBM by each schedule (DESIGN/SPerf)."""
    base = (
        2 * (m * k + k * n)        # AR,AI + BR,BI int8 reads
        + (m * k + k * n)          # (AR+AI), (BR+BI) int8 write+read
        + 3 * 4 * m * n * 2        # D,E,F int32 write + read back
        + 2 * m * n                # CR, CI int8 writes
    )
    fused = 2 * (m * k + k * n) + 2 * m * n
    return base, fused


def check_launch_counts(m: int, n: int, k: int, n_moduli: int) -> int:
    """Count `pallas_call`s of the full batched pipelines and compare with
    the perfmodel; returns the number of mismatches (0 = pass)."""
    rng = np.random.default_rng(0)
    a = jnp.asarray((rng.random((m, k)) - 0.5).astype(np.float32))
    b = jnp.asarray((rng.random((k, n)) - 0.5).astype(np.float32))
    ca = jnp.asarray(
        ((rng.random((m, k)) - 0.5) + 1j * (rng.random((m, k)) - 0.5)).astype(
            np.complex64
        )
    )
    cb = jnp.asarray(
        ((rng.random((k, n)) - 0.5) + 1j * (rng.random((k, n)) - 0.5)).astype(
            np.complex64
        )
    )
    def kpol(backend, **kw):
        return GemmPolicy(
            backend=backend, n_moduli=n_moduli, execution="kernel",
            interpret=True, **kw,
        )

    def fpol(backend, **kw):
        return GemmPolicy(
            backend=backend, n_moduli=n_moduli, execution="fused",
            interpret=True, **kw,
        )

    cases = [
        (
            "real",
            lambda x, y: linalg.matmul(x, y, policy=kpol("ozaki2_f32")),
            (a, b),
            perfmodel.kernel_launch_count(n_moduli, "real"),
        ),
        (
            "karatsuba",
            lambda x, y: linalg.matmul(x, y, policy=kpol("ozaki2_c64")),
            (ca, cb),
            perfmodel.kernel_launch_count(n_moduli, "karatsuba"),
        ),
        (
            "block_a",
            lambda x, y: linalg.matmul(
                x, y, policy=kpol("ozaki2_c64", formulation="block_a")
            ),
            (ca, cb),
            perfmodel.kernel_launch_count(n_moduli, "block_a"),
        ),
        # the megakernel: cast + products + Garner share ONE pallas_call —
        # the whole point of execution='fused' (4 -> 1 vs the kernel path)
        (
            "fused_real",
            lambda x, y: linalg.matmul(x, y, policy=fpol("ozaki2_f32")),
            (a, b),
            perfmodel.kernel_launch_count(n_moduli, "real", fused=True),
        ),
        (
            "fused_karatsuba",
            lambda x, y: linalg.matmul(x, y, policy=fpol("ozaki2_c64")),
            (ca, cb),
            perfmodel.kernel_launch_count(n_moduli, "karatsuba", fused=True),
        ),
    ]
    bad = 0
    for name, fn, operands, expect in cases:
        got = count_pallas_launches(fn, *operands)
        ok = got == expect
        if name.startswith("fused"):
            # the fused path must actually *reduce* launches, not merely
            # match its own model row
            ok = ok and got == 1 and got < perfmodel.kernel_launch_count(
                n_moduli, name.removeprefix("fused_")
            )
        bad += not ok
        emit(
            f"kernel_fusion/launches/{name}/{m}x{n}x{k}/N={n_moduli}",
            0.0,
            f"pallas_calls={got};model={expect};ok={int(ok)}",
        )
    return bad


def run(m: int = 256, n: int = 256, k: int = 512, p: int = 251,
        n_moduli: int = 5):
    rng = np.random.default_rng(0)
    h = (p - 1) // 2
    mats = [
        jnp.asarray(rng.integers(-h, h + 1, size=s).astype(np.int8))
        for s in [(m, k), (m, k), (k, n), (k, n)]
    ]

    def unfused(ar, ai, br, bi):
        return kref.karatsuba_mod_gemm_ref(ar, ai, br, bi, p=p)

    def fused(ar, ai, br, bi):
        return karatsuba_mod_gemm(ar, ai, br, bi, p=p, interpret=True)

    cost_u = jax.jit(unfused).lower(*mats).compile().cost_analysis()
    if isinstance(cost_u, (list, tuple)):  # jax < 0.4.34 returns one per device
        cost_u = cost_u[0] if cost_u else {}
    bytes_u = float(cost_u.get("bytes accessed", 0))
    flops_u = float(cost_u.get("flops", 0))
    base, fmodel = analytic(m, n, k)
    emit(
        f"kernel_fusion/unfused/{m}x{n}x{k}",
        0.0,
        f"hlo_bytes={bytes_u:.3e};hlo_flops={flops_u:.3e};"
        f"model_hbm_bytes={base:.3e}",
    )
    emit(
        f"kernel_fusion/fused/{m}x{n}x{k}",
        0.0,
        f"model_hbm_bytes={fmodel:.3e};reduction={base / fmodel:.2f}x"
        f";note=pallas kernel shares A/B tiles in VMEM, no int32 HBM roundtrip",
    )
    # correctness of the fused kernel at this shape (bit-exact)
    cu = unfused(*mats)
    cf = fused(*mats)
    ok = bool(jnp.all(cu[0] == cf[0]) and jnp.all(cu[1] == cf[1]))
    emit(f"kernel_fusion/exactness/{m}x{n}x{k}", 0.0, f"bit_exact={int(ok)}")
    bad = check_launch_counts(m, n, k, n_moduli)
    if not ok or bad:
        raise SystemExit(
            f"kernel_fusion regression: bit_exact={ok}, launch mismatches={bad}"
        )


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--smoke", action="store_true",
        help="tiny shapes for the CI interpret-mode launch-count check",
    )
    args = ap.parse_args()
    if args.smoke:
        run(m=32, n=24, k=64, p=251, n_moduli=4)
    else:
        run()
