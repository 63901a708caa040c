"""Paper Figs. 6-13: emulated CGEMM/ZGEMM throughput.

Two outputs per configuration:
  * the paper's performance-model projection (SIII-C) on TPU v5e and on the
    paper's four GPUs — these reproduce the shape of Figs. 6-13 (TFLOPS vs
    size vs N) and the speedup-over-native claims;
  * measured wall-time of the actual emulation on this host (CPU) at small
    sizes, demonstrating the harness end-to-end.

Key reproduced claims (checked in the derived column):
  - B200 fast-N speedups over native ZGEMM of ~4-5.6x at N in [13,18];
  - Ozaki-II with N moduli beats Ozaki-I with S~N slices by ~S(S+1)/2/N x;
  - on v5e there is NO native ZGEMM — emulation is the only route (DESIGN).

CLI (the tracked-throughput harness; `benchmarks.run` still calls `run()`):

    PYTHONPATH=src python -m benchmarks.bench_throughput \
        [--smoke] [--execution reference|kernel|sharded|fp8|fused] \
        [--residue R] [--mesh DxM] [--json BENCH_throughput.json] [--force] \
        [--calibrate off|load|run] [--compare BASELINE.json]

`--execution` picks the residue backend the measured section times
(`sharded` builds a host mesh — run under
XLA_FLAGS=--xla_force_host_platform_device_count=N to span N devices;
`fp8` runs the e4m3 digit-GEMM engine; `fused` the one-launch megakernel)
and every measured record reports BOTH aggregate and per-device GEMM
throughput, written to the `--json` file keyed by the full measurement
config (execution, mesh, devices, name) plus the active calibration-cache
stamp — re-running replaces exactly the re-measured keys, so
BENCH_throughput.json accumulates the kernel-vs-fused (and fp8/sharded,
and tuned-vs-default-block) trajectories side by side; records it cannot
key-match are never dropped without `--force`.

`--calibrate load|run` activates a `repro.tune` calibration cache before
measuring, so the Pallas executions launch the autotuned block shapes
(records are stamped with the cache hash).  `--compare baseline.json`
diffs this run against a previous run's records by measurement config and
exits nonzero when any per-device throughput regresses more than
`--tolerance` (default 15%) — the CI guard that tuned blocks never ship
slower than the static defaults.
"""
from __future__ import annotations

import argparse
import functools
import json

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import ozaki2_cgemm
from repro.core.perfmodel import (
    B200,
    GH200,
    HARDWARE,
    TPU_V5E,
    complex_tflops,
    engine_time_s,
    ozaki1_complex_time_s,
    complex_time_s,
    select_engine,
)

from .common import emit, phi_matrix, time_fn


def model_tables():
    sizes = (1024, 2048, 4096, 8192, 16384)
    for hw in (TPU_V5E, B200, GH200):
        for prec, n_range in (("c", (6, 7, 8, 9)), ("z", (13, 14, 16, 18))):
            for nm in n_range:
                tf = [complex_tflops(s, s, s, nm, hw, "fast", prec) for s in sizes]
                native = hw.native_c64 if prec == "c" else hw.native_c128
                speed = tf[-1] * 1e12 / native if native else float("inf")
                emit(
                    f"fig6_13/model/{hw.name}/{prec}gemm/fast-{nm}",
                    0.0,
                    "tflops=" + "/".join(f"{t:.0f}" for t in tf)
                    + f";speedup_vs_native@16k={speed:.2f}",
                )
    # int8-vs-fp8 engine projections (arXiv:2603.10634 comparison): the fp8
    # engine runs 4 digit-GEMM volumes at the e4m3 rate, so it wins only
    # where the rate advantage or memory-boundedness beats the 4x volume
    for hw in (TPU_V5E, B200, GH200):
        for s in (2048, 16384):
            t_i8 = engine_time_s("int8", s, s, s, 14, hw, "fast", "z")
            t_f8 = engine_time_s("fp8", s, s, s, 14, hw, "fast", "z")
            emit(
                f"engine/model/{hw.name}/zgemm/fast-14/{s}",
                0.0,
                f"int8_s={t_i8:.2e};fp8_s={t_f8:.2e};"
                f"fp8_over_int8={t_f8 / t_i8:.2f}x;"
                f"selected={select_engine(s, s, s, 14, hw, 'fast', 'z')}",
            )
    # Ozaki-I comparison (GH200, z, 16384): paper SIV-B
    for s in (7, 8, 9):
        t1 = ozaki1_complex_time_s(16384, 16384, 16384, s, GH200)
        t2 = complex_time_s(16384, 16384, 16384, 13, GH200, "fast", "z")
        emit(
            f"fig10/ozaki1_vs_2/slices{s}",
            0.0,
            f"ozakiII_speedup={t1 / t2:.2f}x;paper_band=2.5-5.5x",
        )


def ozaki1_measured(s: int = 192):
    """Both schemes measured on OUR implementations at equal accuracy."""
    import numpy as np

    from repro.core import ozaki2_cgemm
    from repro.core.ozaki1 import int8_gemm_count, ozaki1_cgemm

    rng = np.random.default_rng(3)
    a = jnp.asarray(phi_matrix(rng, (s, s), 1.0, np.complex128))
    b = jnp.asarray(phi_matrix(rng, (s, s), 1.0, np.complex128))
    ref = np.asarray(a).astype(np.clongdouble) @ np.asarray(b).astype(np.clongdouble)

    def err(c):
        return float(np.max(np.abs(np.asarray(c) - ref) / np.abs(ref).max()))

    c1 = ozaki1_cgemm(a, b, 9)
    c2 = ozaki2_cgemm(a, b, 14, "fast")
    emit(
        f"fig10/measured/ozaki1_s9/{s}",
        0.0,
        f"maxrel={err(c1):.2e};int8_gemms={3 * int8_gemm_count(9)}",
    )
    emit(
        f"fig10/measured/ozaki2_n14/{s}",
        0.0,
        f"maxrel={err(c2):.2e};int8_gemms={3 * 14};"
        f"gemm_ratio={3 * int8_gemm_count(9) / (3 * 14):.2f}x",
    )


def measured(sizes=(256, 512)):
    rng = np.random.default_rng(1)
    for s in sizes:
        a = jnp.asarray(phi_matrix(rng, (s, s), 0.5, np.complex64))
        b = jnp.asarray(phi_matrix(rng, (s, s), 0.5, np.complex64))
        for nm in (6, 8):
            fn = functools.partial(ozaki2_cgemm, n_moduli=nm, mode="fast")
            us = time_fn(fn, a, b)
            emit(
                f"fig6_13/measured_cpu/cgemm/fast-{nm}/{s}",
                us,
                f"tflops={8 * s**3 / (us * 1e-6) * 1e-12:.4f}",
            )
        us_n = time_fn(jnp.matmul, a, b)
        emit(
            f"fig6_13/measured_cpu/cgemm/native/{s}",
            us_n,
            f"tflops={8 * s**3 / (us_n * 1e-6) * 1e-12:.4f}",
        )


def _bench_mesh(execution: str, residue: int, mesh_arg: str | None):
    """The mesh a sharded measured section spans (None off the sharded path)."""
    if execution != "sharded":
        return None
    from repro.launch.mesh import make_host_mesh

    if mesh_arg:
        d, m = map(int, mesh_arg.split("x"))
        return jax.make_mesh(
            (d, m, max(residue, 1)), ("data", "model", "residue")
        )
    return make_host_mesh(
        1, 1, residue=residue if residue > 1 else len(jax.devices())
    )


# (blas-prefix, backend, numpy dtype, flops per m*n*k) measured per mode —
# one real and one complex class keeps the tracked trajectory per dtype x
# mode without quadrupling bench wall-time (f64/c128 follow the same code
# paths at higher N).
_MEASURED_CLASSES = (
    ("s", "ozaki2_f32", np.float32, 2.0),
    ("c", "ozaki2_c64", np.complex64, 8.0),
)


def measured_policy(
    sizes=(256, 512),
    execution: str = "reference",
    residue: int = 1,
    mesh_arg: str | None = None,
    records: list | None = None,
    rtol: float | None = None,
):
    """Measured wall-time of the policy-routed emulation on this host.

    Covers dtype class x scaling mode (sgemm/cgemm x fast/accu) so the
    tracked records pin the whole measured surface per execution.  Reports
    aggregate TFLOPS (whole-GEMM flops / wall time) and per-device TFLOPS
    (aggregate / devices the mesh spans) for every configuration — the
    number that must stay flat as the mesh grows is per-device, and the one
    that must grow is aggregate.

    With `rtol` the policies run accuracy-adaptive (`GemmPolicy(rtol=...)`:
    fewest moduli provably meeting the tolerance instead of the per-dtype
    defaults); the records carry an `/rtol...` name suffix so the adaptive
    trajectory coexists with the default one in the tracked JSON.
    """
    import repro
    from repro import linalg
    from repro.core import GemmPolicy
    from repro.tune.cache import calibration_hash, current_calibration

    cal = current_calibration()
    cal_stamp = calibration_hash(cal) if cal is not None else None
    mesh = _bench_mesh(execution, residue, mesh_arg)
    n_dev = int(np.prod(list(mesh.shape.values()))) if mesh is not None else 1
    mesh_name = (
        "x".join(str(s) for s in mesh.shape.values()) if mesh is not None else "1"
    )
    rng = np.random.default_rng(1)
    for s in sizes:
        for prec, backend, dt, flop in _MEASURED_CLASSES:
            a = jnp.asarray(phi_matrix(rng, (s, s), 0.5, dt))
            b = jnp.asarray(phi_matrix(rng, (s, s), 0.5, dt))
            for mode in ("fast", "accu"):
                pol = GemmPolicy(
                    backend=backend, mode=mode, execution=execution,
                    mesh=mesh, rtol=rtol,
                )
                suffix = "" if rtol is None else f"/rtol{rtol:g}"
                us = time_fn(
                    functools.partial(linalg.matmul_jit, policy=pol), a, b
                )
                agg = flop * s**3 / (us * 1e-6) * 1e-12
                emit(
                    f"fig6_13/measured_cpu/{prec}gemm/{execution}"
                    f"/mesh{mesh_name}/{mode}/{s}{suffix}",
                    us,
                    f"tflops_aggregate={agg:.4f}"
                    f";tflops_per_device={agg / n_dev:.4f}",
                )
                if records is not None:
                    records.append({
                        "name": f"{prec}gemm/{mode}/{s}{suffix}",
                        "execution": execution,
                        "mesh": mesh_name,
                        "devices": n_dev,
                        "us_per_call": us,
                        "tflops_aggregate": agg,
                        "tflops_per_device": agg / n_dev,
                        "calibration": cal_stamp,
                    })


def run():
    model_tables()
    measured()
    ozaki1_measured()


def record_key(r):
    """Dedupe key of one tracked record, or None if unreadable.

    The measurement config (execution, mesh, devices, name) plus the
    calibration-cache stamp — tuned and untuned runs of the same config are
    distinct trajectories and must coexist in the JSON.
    """
    try:
        key = (r["execution"], r["mesh"], r["devices"], r["name"])
    except (KeyError, TypeError):
        return None
    return key + (r.get("calibration"),)


def merge_records(old, new, *, force: bool = False):
    """Merge `new` measured records into the `old` tracked list.

    A record is replaced only when this run re-measured its exact
    `record_key` — a kernel run must not clobber the fused/fp8/sharded
    runs, a 2x2-mesh run must not clobber the 1x8 trajectory of the same
    execution, and a calibrated run must not clobber the untuned baseline.
    Old records are also deduped among themselves (same key: last one
    wins), so a file that accumulated duplicates is repaired on rewrite.
    Records whose key cannot be read (foreign or pre-key schema) are never
    dropped silently: that raises with a hint unless `force`.
    """
    unkeyed = [r for r in old if record_key(r) is None]
    if unkeyed and not force:
        raise SystemExit(
            f"--json target holds {len(unkeyed)} records without an "
            "(execution, mesh, devices, name) key; refusing to silently "
            "overwrite them — re-run with --force to drop, or point "
            "--json at a fresh file"
        )
    new_keys = {record_key(r) for r in new}
    kept: dict = {}
    for r in old:
        k = record_key(r)
        if k is not None and k not in new_keys:
            kept[k] = r
    return list(kept.values()) + list(new)


def compare_records(records, baseline, *, tolerance: float = 0.15):
    """Regression strings for records slower than the baseline run.

    Matches by measurement config (execution, mesh, devices, name) —
    deliberately ignoring the calibration stamp, so a tuned run is held to
    the untuned baseline's bar — and takes the best (max) per-device
    throughput over baseline duplicates.  A record is a regression when
    its tflops_per_device drops more than `tolerance` (fractional) below
    that.  Configs absent from the baseline are skipped (new coverage is
    not a regression).
    """
    best: dict = {}
    for r in baseline:
        k = record_key(r)
        if k is None:
            continue
        v = r.get("tflops_per_device")
        if v is None or not np.isfinite(v) or v <= 0:
            continue
        k = k[:4]
        best[k] = max(best.get(k, 0.0), float(v))
    regressions = []
    for r in records:
        k = record_key(r)
        if k is None or k[:4] not in best:
            continue
        base = best[k[:4]]
        cur = float(r["tflops_per_device"])
        if cur < (1.0 - tolerance) * base:
            regressions.append(
                f"{'/'.join(map(str, k[:4]))}: {cur:.4f} tflops/device vs "
                f"baseline {base:.4f} ({cur / base - 1.0:+.1%}, "
                f"tolerance -{tolerance:.0%})"
            )
    return regressions


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes (CI: proves the path end-to-end)")
    ap.add_argument("--execution", default="reference",
                    choices=["reference", "kernel", "per_modulus_kernel",
                             "sharded", "fp8", "fused"],
                    help="residue backend the measured section times "
                         "(fp8: the e4m3 digit-GEMM engine; fused: the "
                         "one-launch megakernel)")
    ap.add_argument("--force", action="store_true",
                    help="allow --json to drop existing records it cannot "
                         "key-match (foreign/older record schema)")
    ap.add_argument("--residue", type=int, default=1,
                    help="residue mesh-axis size (sharded execution)")
    ap.add_argument("--mesh", default=None,
                    help="DxM data/model layout for the sharded mesh")
    ap.add_argument("--rtol", type=float, default=None,
                    help="measure accuracy-adaptive policies "
                         "(GemmPolicy(rtol=...): fewest moduli provably "
                         "meeting this componentwise tolerance) instead of "
                         "the per-dtype moduli defaults")
    ap.add_argument("--json", default="BENCH_throughput.json",
                    help="write measured records here (tracked throughput)")
    ap.add_argument("--compare", default=None, metavar="BASELINE.json",
                    help="after measuring, diff this run's records against "
                         "the records in BASELINE.json by (execution, mesh, "
                         "devices, name) and exit nonzero when any "
                         "per-device throughput regresses more than "
                         "--tolerance (the JSON is still written first)")
    ap.add_argument("--tolerance", type=float, default=0.15,
                    help="fractional throughput drop --compare tolerates "
                         "before failing (default 0.15)")
    from repro.tune.cli import add_calibration_args, apply_calibration_args

    add_calibration_args(ap)
    args = ap.parse_args()
    apply_calibration_args(args, smoke=args.smoke)

    sizes = (48, 96) if args.smoke else (256, 512)
    records: list = []
    if not args.smoke:
        model_tables()
    measured_policy(
        sizes, args.execution, args.residue, args.mesh, records,
        rtol=args.rtol,
    )
    if args.json:
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
    # CI contract: the run must produce finite nonzero throughput records
    # (an explicit raise, not an assert — CI must fail under python -O too)
    bad = [
        r for r in records
        if not (np.isfinite(r["tflops_aggregate"])
                and np.isfinite(r["tflops_per_device"])
                and r["tflops_per_device"] > 0)
    ]
    if not records or bad:
        raise SystemExit(
            f"bench_throughput produced no usable records: {bad or 'empty'}"
        )
    if args.compare:
        try:
            with open(args.compare) as f:
                baseline = json.load(f).get("records", [])
        except (OSError, ValueError) as e:
            raise SystemExit(f"--compare baseline {args.compare!r}: {e}")
        regressions = compare_records(
            records, baseline, tolerance=args.tolerance
        )
        for line in regressions:
            print(f"REGRESSION {line}")
        matched = sum(
            1 for r in records
            if record_key(r) is not None
            and record_key(r)[:4] in {
                record_key(b)[:4] for b in baseline
                if record_key(b) is not None
            }
        )
        print(
            f"bench_throughput --compare: {matched}/{len(records)} records "
            f"matched against {args.compare}; {len(regressions)} "
            f"regression(s) beyond -{args.tolerance:.0%}"
        )
        if regressions:
            raise SystemExit(2)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
