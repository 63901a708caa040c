"""Readings behind the limits of a cell's check (bench/limits/<cell>.json).

    python3 bench/readings.py --workload cgemm_fast_n7.sq8192 \
        --seeds 11,12,13 --control-seeds 21,22,23 --seconds 2 --out r.json

Runs the cell on the chip, in one process, once per seed with the program
and once per control seed with the control in its place (bench/control.py),
each with a short window at the cell's own size, and writes every run's
compared numbers.  The lower reading of a number is the largest the
program gives; the upper reading the smallest the control gives.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from bench import harness
    from bench.control import control_entry

    runs = []
    plan = [(int(s), None) for s in args.seeds.split(",") if s]
    plan += [(int(s), control_entry) for s in args.control_seeds.split(",") if s]
    for seed, fault in plan:
        res = harness.run(args.workload, seed, args.seconds, False,
                          t_start=time.perf_counter(), fault=fault)
        row = {"seed": seed, "control": fault is not None,
               "correct": res["correct"], "calls": res["attempted"],
               **{k: v["value"] for k, v in res["checks"].items()}}
        print(json.dumps(row), flush=True)
        runs.append(row)
    summary = {}
    for key in runs[0]:
        if key in ("seed", "control", "correct", "calls"):
            continue
        prog = [r[key] for r in runs if not r["control"] and key in r]
        ctrl = [r[key] for r in runs if r["control"] and key in r]
        summary[key] = {"lower": max(prog) if prog else None,
                        "upper": min(ctrl) if ctrl else None}
    print(json.dumps({"workload": args.workload, "summary": summary}), flush=True)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump({"workload": args.workload, "runs": runs, "summary": summary}, fh,
                  indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
