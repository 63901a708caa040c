"""Peak device memory in use, read from the device runtime after the window."""
NAME, UNIT, BETTER, SOURCE = "peak_hbm_gib", "GiB", "lower", "device_trace"


def read(run):
    return None if run.peak_bytes is None else run.peak_bytes / 2**30
