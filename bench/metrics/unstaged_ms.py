"""Device time per call of the ops that carry no `ozaki2.<stage>` scope in
a program that names its stages: ops the compiler makes with no source in
a stage (bench/stages.py)."""
from bench import stages

NAME, UNIT, BETTER, SOURCE = "unstaged_ms", "ms", "lower", "device_trace"
LAYER, MOVES = "scaling and assembly", "tflops"


def read(run):
    return stages.per_call_ms(run, stages.UNSTAGED)
