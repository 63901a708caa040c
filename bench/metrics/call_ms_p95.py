"""95th percentile of the latency of every call in the window (host clock,
each call blocked on before the next is sent)."""
import numpy as np

NAME, UNIT, BETTER, SOURCE = "call_ms_p95", "ms", "lower", "host_clock"


def read(run):
    return float(np.percentile(np.asarray(run.latencies_s) * 1e3, 95))
