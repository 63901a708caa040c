"""`cast_ms` where the cell reports `call_ms_p95`, not `tflops`."""
from bench.metrics.cast_ms import read  # noqa: F401

NAME, UNIT, BETTER, SOURCE = "cast_ms.latency", "ms", "lower", "device_trace"
LAYER, MOVES = "residue cast", "call_ms_p95"
