"""`outside_kernels_ms` where the cell reports `call_ms_p95`, not `tflops`."""
from bench.metrics.outside_kernels_ms import read  # noqa: F401

NAME, UNIT, BETTER, SOURCE = "outside_kernels_ms.latency", "ms", "lower", "device_trace"
LAYER, MOVES = "scaling and assembly", "call_ms_p95"
