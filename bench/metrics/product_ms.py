"""Device time per call of the residue-product kernels (int8 in, int8 out)."""
NAME, UNIT, BETTER, SOURCE = "product_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "residue products", "tflops"


def read(run):
    return None if run.trace is None else run.trace.per_call_ms("product")
