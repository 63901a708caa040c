"""python -m repro.tune — run the calibration microbench + block autotuner.

Measures the live backend (`repro.tune.calibrate`), autotunes the Pallas
block shapes (`repro.tune.autotune`), and persists both to the calibration
cache.  CI runs this in ``--smoke`` mode (the `tier1-tune` job) and then
re-certifies the full policy matrix with the cache loaded::

    PYTHONPATH=src python -m repro.tune --smoke --out calibration.json
    PYTHONPATH=src python -m repro.analysis --matrix smoke \\
        --calibration calibration.json
"""
from __future__ import annotations

import argparse
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.tune",
        description="one-shot on-device calibration + Pallas block autotune",
    )
    ap.add_argument("--smoke", action="store_true",
                    help="tiny probes/shapes (CI: seconds on a CPU host; "
                         "numbers are noisy but structurally valid)")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="cache file to write (default: the per-backend "
                         "default_cache_path())")
    ap.add_argument("--no-blocks", dest="blocks", action="store_false",
                    help="skip the block autotuner (measure HW only)")
    ap.add_argument("-v", "--verbose", action="store_true",
                    help="print every measurement and candidate timing")
    args = ap.parse_args(argv)

    import repro  # noqa: F401  (x64 on, matching every other entry point)
    from ..launch.compile_cache import enable_compile_cache
    from .cache import calibration_hash, default_cache_path, save_calibration
    from .calibrate import calibrate

    enable_compile_cache()
    cal = calibrate(smoke=args.smoke, blocks=args.blocks,
                    verbose=args.verbose)
    path = save_calibration(cal, args.out or default_cache_path())
    print(
        f"repro.tune: calibrated {cal.device_kind} x{cal.device_count} "
        f"(jax {cal.jax_version}) -> {path}\n"
        f"  hw: mem_bw={cal.hw.mem_bw:.3e} B/s int8={cal.hw.int8_ops:.3e} "
        f"OPS fp8={cal.hw.fp8_ops:.3e} OPS "
        f"launch={cal.hw.gemm_launch_s:.2e} s\n"
        f"  blocks: {len(cal.blocks)} tuned slots; "
        f"cache hash {calibration_hash(cal)}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
