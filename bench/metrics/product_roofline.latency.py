"""`product_roofline` where the cell reports `call_ms_p95`, not `tflops`."""
from bench.metrics.product_roofline import read  # noqa: F401

NAME, UNIT, BETTER, SOURCE = "product_roofline.latency", "%", "higher", "device_trace"
LAYER, MOVES = "residue products", "call_ms_p95"
