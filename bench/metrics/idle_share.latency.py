"""`idle_share` where the cell reports `call_ms_p95`, not `tflops`."""
from bench.metrics.idle_share import read  # noqa: F401

NAME, UNIT, BETTER, SOURCE = "idle_share.latency", "%", "lower", "device_trace"
LAYER, MOVES = "device", "call_ms_p95"
