"""`product_ms` where the cell reports `call_ms_p95`, not `tflops`."""
from bench.metrics.product_ms import read  # noqa: F401

NAME, UNIT, BETTER, SOURCE = "product_ms.latency", "ms", "lower", "device_trace"
LAYER, MOVES = "residue products", "call_ms_p95"
