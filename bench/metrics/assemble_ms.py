"""Device time per call of the ops the program runs under its
`ozaki2.assemble` scope: the planar split, the complex output, n-block
slices and their concatenation (bench/stages.py reads the scope)."""
from bench import stages

NAME, UNIT, BETTER, SOURCE = "assemble_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "scaling and assembly", "tflops"


def read(run):
    return stages.per_call_ms(run, "assemble")
