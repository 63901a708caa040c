"""Benchmark harness entry point — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (us_per_call == 0.0 for model-based
rows).  Usage:  PYTHONPATH=src python -m benchmarks.run [--quick]
"""
import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="smaller sweeps")
    args = ap.parse_args()

    import repro  # noqa: F401 (x64 for the numeric core)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from . import (
        bench_accuracy,
        bench_fig1_strategies,
        bench_kernel_fusion,
        bench_perf_model,
        bench_real_supplement,
        bench_throughput,
        roofline,
    )

    print("name,us_per_call,derived")
    t0 = time.time()
    sections = [
        ("fig1", lambda: bench_fig1_strategies.run(h=256 if args.quick else 512)),
        ("fig2-3", bench_perf_model.run),
        (
            "fig4-5",
            lambda: bench_accuracy.run(k=512 if args.quick else 2048),
        ),
        ("fig6-13", bench_throughput.run),
        ("sIV-C", bench_real_supplement.run),
        ("kernel-fusion", bench_kernel_fusion.run),
        ("roofline", roofline.run),
    ]
    for name, fn in sections:
        print(f"# --- {name} ---", file=sys.stderr)
        fn()  # a failed section fails the run
    print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
