"""Entry point of the benchmark: one run of one cell of BENCHMARK.json.

    python3 bench/run.py --workload cgemm_fast_n7.sq8192 --seed 7 \
        --seconds 20 --trace 0

Exits non-zero, printing no result, without a TPU or with fewer chips than
the cell asks for.  The last line of standard output is the result object.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    result = harness.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
