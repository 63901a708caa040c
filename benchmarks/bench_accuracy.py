"""Paper Figs. 4-5: max componentwise error of the emulation vs N and phi —
promoted to a tracked accuracy harness.

Reference products use extended precision (longdouble on x86 = 80-bit,
below double-double but far beyond the f64/f32 targets).  Native (jnp
matmul) errors are reported on the same scale so the 'comparable accuracy'
bands of the paper can be read off directly (red/italic entries in
Figs. 4-5).

Every row is measured through the policy-routed deployment path
(`repro.linalg.matmul` under a `GemmPolicy`) on the certified error metric
`core.accuracy.rel_error` — max_ij |C - C_emul|_ij / (k * amax_i * bmax_j),
the metric the static `core.accuracy.rel_bound` provably bounds — and each
record carries that bound next to the measurement.  Adaptive rows
(`GemmPolicy(mode="auto", rtol=...)`) additionally record the tolerance the
policy resolved for, which the measurement must meet.

CLI (mirrors bench_throughput's tracked-JSON contract):

    PYTHONPATH=src python -m benchmarks.bench_accuracy \
        [--smoke] [--execution reference|kernel|...] \
        [--json BENCH_accuracy.json] [--force]

Records are keyed by (execution, mesh, devices, name) plus the calibration
stamp — `merge_records` / `record_key` are shared with bench_throughput —
so re-running replaces exactly the re-measured keys and BENCH_accuracy.json
accumulates the per-execution accuracy trajectory alongside the perf one.

`check_records` asserts the three invariants CI pins (tests/test_accuracy.py
runs the smoke sweep through it):

  * every measured error <= its static `rel_bound` (the paper-bound
    certificate, end to end);
  * every adaptive row's error <= its requested rtol;
  * every (dtype, mode, n_moduli) cell stays inside its pinned golden
    band (`BANDS`) — a regression alarm ~8x above the currently measured
    error, far below the static bound.
"""
from __future__ import annotations

import argparse
import json

import numpy as np
import jax.numpy as jnp

from repro import linalg
from repro.core import GemmPolicy, rel_bound, rel_error
from repro.core.policy import BACKEND_FOR_DTYPE

from .common import emit, phi_matrix

#: (dtype, phis, moduli counts) — the full Figs. 4-5 sweep plus the real
#: dtype classes the policy stack also serves
FULL_SWEEP = (
    ("float32", (0.5, 1.5), (4, 6, 8)),
    ("float64", (0.5, 2.0), (8, 12, 16)),
    ("complex64", (0.0, 0.5, 1.0, 1.5), tuple(range(3, 10))),
    ("complex128", (0.5, 1.0, 2.0, 4.0), tuple(range(9, 18))),
)
FULL_SHAPE = (128, 2048, 128)  # (m, k, n)

#: the tier-1 profile: small shapes, the band-pinned moduli counts
SMOKE_SWEEP = (
    ("float32", (0.5, 1.5), (4, 6)),
    ("float64", (0.5, 2.0), (8, 12)),
    ("complex64", (0.5, 1.5), (4, 6, 8)),
    ("complex128", (0.5, 2.0), (10, 14)),
)
SMOKE_SHAPE = (32, 96, 24)

#: adaptive rows: requested componentwise tolerance per dtype (mode="auto")
ADAPTIVE_RTOL = {
    "float32": 1e-4,
    "float64": 1e-9,
    "complex64": 1e-4,
    "complex128": 1e-9,
}

#: pinned golden error bands for the smoke sweep, per (dtype, mode,
#: n_moduli): the worst `rel_error` measured across the smoke phis with
#: ~8x headroom.  A measurement above its band is a regression finding even
#: when it still sits below the (much looser) static bound.
BANDS = {
    ("float32", "fast", 4): 2.0e-04,
    ("float32", "fast", 6): 1.0e-06,
    ("float32", "accu", 4): 1.2e-04,
    ("float32", "accu", 6): 5.0e-07,
    ("float64", "fast", 8): 3.0e-09,
    ("float64", "fast", 12): 6.5e-14,
    ("float64", "accu", 8): 1.5e-09,
    ("float64", "accu", 12): 4.5e-14,
    ("complex64", "fast", 4): 3.0e-04,
    ("complex64", "fast", 6): 1.2e-06,
    ("complex64", "fast", 8): 1.2e-08,
    ("complex64", "accu", 4): 1.8e-04,
    ("complex64", "accu", 6): 6.0e-07,
    ("complex64", "accu", 8): 1.2e-08,
    ("complex128", "fast", 10): 2.5e-11,
    ("complex128", "fast", 14): 5.5e-16,
    ("complex128", "accu", 10): 1.5e-11,
    ("complex128", "accu", 14): 2.6e-16,
}


def _longdouble_ref(a, b):
    ld = (
        np.clongdouble
        if np.issubdtype(a.dtype, np.complexfloating)
        else np.longdouble
    )
    return a.astype(ld) @ b.astype(ld)


def sweep(
    shape=SMOKE_SHAPE,
    profile=SMOKE_SWEEP,
    execution: str = "reference",
    seed: int = 7,
) -> list:
    """Measure the profile through the policy-routed path; returns records.

    One record per (dtype, mode, n_moduli, phi) cell plus one adaptive
    (mode="auto", rtol) row per dtype, each carrying the measured
    `rel_error`, the static `rel_bound` and (adaptive rows) the rtol.
    """
    from repro.tune.cache import calibration_hash, current_calibration

    cal = current_calibration()
    cal_stamp = calibration_hash(cal) if cal is not None else None
    m, k, n = shape
    rng = np.random.default_rng(seed)
    records: list = []

    def record(name, dtype_name, mode, nm, phi, err, bound, **extra):
        rec = {
            "name": name,
            "execution": execution,
            "mesh": "1",
            "devices": 1,
            "dtype": dtype_name,
            "mode": mode,
            "n_moduli": nm,
            "phi": phi,
            "k": k,
            "err": err,
            "bound": bound,
            "calibration": cal_stamp,
        }
        rec.update(extra)
        records.append(rec)
        return rec

    for dtype_name, phis, n_range in profile:
        dt = np.dtype(dtype_name)
        backend = BACKEND_FOR_DTYPE[dtype_name]
        for phi in phis:
            a = phi_matrix(rng, (m, k), phi, dt)
            b = phi_matrix(rng, (k, n), phi, dt)
            ref = _longdouble_ref(a, b)
            nat = rel_error(np.asarray(jnp.asarray(a) @ jnp.asarray(b)), ref, a, b)
            emit(
                f"fig45/{dtype_name}/native/phi{phi:g}", 0.0, f"err={nat:.3e}"
            )
            for mode in ("fast", "accu"):
                for nm in n_range:
                    pol = GemmPolicy(
                        backend=backend, n_moduli=nm, mode=mode,
                        execution=execution,
                    )
                    c = np.asarray(
                        linalg.matmul(jnp.asarray(a), jnp.asarray(b), policy=pol)
                    )
                    err = rel_error(c, ref, a, b)
                    bound = rel_bound(
                        dtype_name, mode, nm, k, formulation=pol.formulation
                    )
                    record(
                        f"fig45/{dtype_name}/{mode}-N{nm}/phi{phi:g}",
                        dtype_name, mode, nm, phi, err, bound,
                        native_err=nat,
                    )
                    emit(
                        f"fig45/{dtype_name}/{mode}-N{nm}/phi{phi:g}",
                        0.0,
                        f"err={err:.3e};bound={bound:.3e};native={nat:.3e};"
                        f"at_native_level={int(err <= nat * 4)}",
                    )

    # adaptive rows: mode="auto" + rtol; the resolved plan must measure
    # within the requested tolerance
    for dtype_name, _, _ in profile:
        rtol = ADAPTIVE_RTOL[dtype_name]
        dt = np.dtype(dtype_name)
        a = phi_matrix(rng, (m, k), 0.5, dt)
        b = phi_matrix(rng, (k, n), 0.5, dt)
        ref = _longdouble_ref(a, b)
        pol = GemmPolicy(
            backend=BACKEND_FOR_DTYPE[dtype_name], mode="auto", rtol=rtol,
            execution=execution,
        )
        resolved = pol.resolve_adaptive(m, k, n)
        c = np.asarray(linalg.matmul(jnp.asarray(a), jnp.asarray(b), policy=pol))
        err = rel_error(c, ref, a, b)
        bound = rel_bound(
            dtype_name, resolved.mode, resolved.n_moduli, k,
            formulation=resolved.formulation,
        )
        record(
            f"fig45/{dtype_name}/auto-rtol{rtol:g}/phi0.5",
            dtype_name, resolved.mode, resolved.n_moduli, 0.5, err, bound,
            rtol=rtol,
        )
        emit(
            f"fig45/{dtype_name}/auto-rtol{rtol:g}/phi0.5",
            0.0,
            f"err={err:.3e};bound={bound:.3e};rtol={rtol:g};"
            f"resolved={resolved.mode}/N{resolved.n_moduli}",
        )
    return records


def check_records(records, bands=None) -> list:
    """The CI invariants over measured records; returns violation strings.

    Empty list = certified: every error below its static bound, every
    adaptive row within its rtol, every pinned (dtype, mode, n_moduli)
    cell inside its golden band.
    """
    bands = BANDS if bands is None else bands
    violations = []
    for r in records:
        name = r.get("name", "?")
        err = r.get("err")
        if err is None:
            continue
        bound = r.get("bound")
        if bound is not None and err > bound:
            violations.append(
                f"{name}: err={err:.3e} EXCEEDS static bound {bound:.3e}"
            )
        rtol = r.get("rtol")
        if rtol is not None and err > rtol:
            violations.append(
                f"{name}: err={err:.3e} exceeds requested rtol={rtol:g}"
            )
        band = bands.get((r.get("dtype"), r.get("mode"), r.get("n_moduli")))
        if band is not None and rtol is None and err > band:
            violations.append(
                f"{name}: err={err:.3e} outside pinned band {band:.3e}"
            )
    return violations


def run(m: int = 128, n: int = 128, k: int = 2048):
    """Legacy harness entry (benchmarks.run): the full Figs. 4-5 sweep."""
    return sweep(shape=(m, k, n), profile=FULL_SWEEP)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1 profile: small shapes, band-pinned cells")
    ap.add_argument("--execution", default="reference",
                    choices=["reference", "kernel", "per_modulus_kernel",
                             "sharded", "fp8", "fused"],
                    help="residue backend the sweep measures through")
    ap.add_argument("--json", default="BENCH_accuracy.json",
                    help="write measured records here (tracked accuracy)")
    ap.add_argument("--force", action="store_true",
                    help="allow --json to drop existing records it cannot "
                         "key-match (foreign/older record schema)")
    args = ap.parse_args()

    if args.smoke:
        records = sweep(SMOKE_SHAPE, SMOKE_SWEEP, execution=args.execution)
    else:
        records = sweep(FULL_SHAPE, FULL_SWEEP, execution=args.execution)
    if args.json:
        from .bench_throughput import merge_records

        try:
            with open(args.json) as f:
                old = json.load(f).get("records", [])
        except FileNotFoundError:
            old = []
        except (OSError, ValueError) as e:
            raise SystemExit(
                f"--json target {args.json!r} exists but is unreadable "
                f"({e}); refusing to overwrite — fix or remove it, or "
                f"point --json elsewhere"
            )
        with open(args.json, "w") as f:
            json.dump(
                {"records": merge_records(old, records, force=args.force)},
                f, indent=1,
            )
    violations = check_records(records, BANDS if args.smoke else {})
    for v in violations:
        print(f"VIOLATION {v}")
    print(
        f"bench_accuracy: {len(records)} records, "
        f"{len(violations)} violation(s)"
    )
    if violations:
        raise SystemExit(2)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
