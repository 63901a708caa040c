"""Record a few calls of one cell under the profiler, for the tests of the
trace readers: the trace and the compiled program's text, both gzipped.

    python3 bench/record_trace.py --workload cgemm_fast_n7.sq1024 --calls 4 \
        --seed 7 --out bench/tests/data

writes `<out>/<cell>.<calls>calls.xplane.pb.gz` and `<out>/<cell>.hlo.txt.gz`.
The calls run as in a traced run of the harness (its host spans, its
profiler options), after two warm-up calls.  Needs the chip.
"""
from __future__ import annotations

import argparse
import gzip
import importlib
import shutil
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--calls", type=int, default=4)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args(argv)

    from bench import harness, stages, trace

    cell = harness.load_cell(args.workload)
    harness.use_cache_dir()
    import jax

    harness.require_chip(cell.chips)
    sys.path.insert(0, str(harness.ROOT / "src"))
    driver = importlib.import_module(f"bench.drivers.{cell.driver}")
    ops = driver.operands(args.seed, cell.config, cell.traffic)
    span = harness.span_factory(True)
    entry = driver.prepare(cell.config, ops, span)
    for _ in range(2):
        entry.call()

    tmp = harness.TRACE_DIR / f"record.{cell.name}"
    shutil.rmtree(tmp, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp), profiler_options=options)
    try:
        for _ in range(args.calls):
            out = None
            with span("call"):
                out = entry.call()
    finally:
        jax.profiler.stop_trace()
    del out

    pb = max(tmp.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    t = trace.reduce_dir(tmp, harness.SPAN_PREFIX)
    text = stages.live_program_text(name for name, _ in t.op_s)
    if text is None:
        raise SystemExit("record_trace: no live program holds the traced ops")
    args.out.mkdir(parents=True, exist_ok=True)
    stem = args.out / f"{cell.name}.{args.calls}calls"
    (stem.parent / (stem.name + ".xplane.pb.gz")).write_bytes(
        gzip.compress(pb.read_bytes(), mtime=0))
    (args.out / f"{cell.name}.hlo.txt.gz").write_bytes(
        gzip.compress(text.encode(), mtime=0))
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"record_trace: {t.summary()}; wrote {stem}.xplane.pb.gz and "
          f"{cell.name}.hlo.txt.gz")
    return 0


if __name__ == "__main__":
    sys.exit(main())
