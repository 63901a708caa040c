"""Useful work over the whole window: the paper's flops per completed call
(8 m n k complex, 2 m n k real) times calls, over the window's length."""
NAME, UNIT, BETTER, SOURCE = "tflops", "TFLOP/s", "higher", "host_clock"


def read(run):
    return run.calls * run.work.useful_flops / run.window_s / 1e12
