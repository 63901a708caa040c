"""`mfu` where the cell reports `call_ms_p95`, not `tflops`."""
from bench.metrics.mfu import read  # noqa: F401

NAME, UNIT, BETTER, SOURCE = "mfu.latency", "%", "higher", "device_trace"
LAYER, MOVES = "whole call", "call_ms_p95"
