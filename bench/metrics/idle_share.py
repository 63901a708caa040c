"""Share of the traced window in which no operation ran on the device."""
NAME, UNIT, BETTER, SOURCE = "idle_share", "%", "lower", "device_trace"
LAYER, MOVES = "device", "tflops"


def read(run):
    t = run.trace
    if t is None:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
