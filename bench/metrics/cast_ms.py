"""Device time per call of the residue-cast kernels (float in, int8 out)."""
NAME, UNIT, BETTER, SOURCE = "cast_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "residue cast", "tflops"


def read(run):
    return None if run.trace is None else run.trace.per_call_ms("cast")
