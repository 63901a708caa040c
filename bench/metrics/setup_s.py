"""Set-up time: process start to the first timed call, compile included."""
NAME, UNIT, BETTER, SOURCE = "setup_s", "s", "lower", "host_clock"


def read(run):
    return run.setup_s
