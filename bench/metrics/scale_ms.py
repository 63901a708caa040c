"""Device time per call of the ops the program runs under its `ozaki2.scale`
scope: the scale exponents (bench/stages.py reads the scope)."""
from bench import stages

NAME, UNIT, BETTER, SOURCE = "scale_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "scaling and assembly", "tflops"


def read(run):
    return stages.per_call_ms(run, "scale")
