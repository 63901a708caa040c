"""Device time per call of every operation that is not a Pallas kernel:
the scaling pass, the f64 assembly of the output and XLA's copies."""
NAME, UNIT, BETTER, SOURCE = "outside_kernels_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "scaling and assembly", "tflops"


def read(run):
    return None if run.trace is None else run.trace.per_call_ms("outside")
