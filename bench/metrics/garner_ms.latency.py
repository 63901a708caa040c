"""`garner_ms` where the cell reports `call_ms_p95`, not `tflops`."""
from bench.metrics.garner_ms import read  # noqa: F401

NAME, UNIT, BETTER, SOURCE = "garner_ms.latency", "ms", "lower", "device_trace"
LAYER, MOVES = "reconstruction", "call_ms_p95"
