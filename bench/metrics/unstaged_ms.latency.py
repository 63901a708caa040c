"""`unstaged_ms` where the cell reports `call_ms_p95`, not `tflops`."""
from bench.metrics.unstaged_ms import read  # noqa: F401

NAME, UNIT, BETTER, SOURCE = "unstaged_ms.latency", "ms", "lower", "device_trace"
LAYER, MOVES = "scaling and assembly", "call_ms_p95"
