"""One module per metric, named as in BENCHMARK.json: `<name>.py`.

Each module states NAME, UNIT, BETTER and SOURCE; a per-layer metric also
states LAYER (as PERF.md's list of layers names it) and MOVES (the
end-to-end metric it should move).  `read(run)` takes the harness's `Run`
record and returns the value, or None where the run holds nothing to read
(the harness then leaves the metric out of the result line).

One quantity reported in cells that report different end-to-end metrics
has one name per metric it moves: `cast_ms` moves `tflops`,
`cast_ms.latency` moves `call_ms_p95`, and the second reuses the first's
reader.
"""
