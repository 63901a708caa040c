"""The residue products' share of their roofline: the least time the chip
could take for the products' operations and bytes (bench/work.py, from
the configuration's shapes, N and formulation), the larger of
ops / int8 peak and bytes / HBM bandwidth, over their device time."""
NAME, UNIT, BETTER, SOURCE = "product_roofline", "%", "higher", "device_trace"
LAYER, MOVES = "residue products", "tflops"


def read(run):
    t = run.trace
    ms = None if t is None else t.per_call_ms("product")
    if not ms:
        return None
    ops, nbytes = run.work.stage("product")
    least_s = max(ops / run.peaks["int8_ops"], nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
