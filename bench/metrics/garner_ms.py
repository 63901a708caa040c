"""Device time per call of the Garner reconstruction kernels (int8 in, float out)."""
NAME, UNIT, BETTER, SOURCE = "garner_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "reconstruction", "tflops"


def read(run):
    return None if run.trace is None else run.trace.per_call_ms("garner")
