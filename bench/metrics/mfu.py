"""Useful flops of the traced window over the chip's int8 peak, the
highest rate the chip has, so the share stays under 100% for any
implementation, native included."""
NAME, UNIT, BETTER, SOURCE = "mfu", "%", "higher", "device_trace"
LAYER, MOVES = "whole call", "tflops"


def read(run):
    t = run.trace
    if t is None or t.calls == 0:
        return None
    rate = t.calls * run.work.useful_flops / t.window_s
    return 100.0 * rate / run.peaks["int8_ops"]
