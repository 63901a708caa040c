"""Paper Figs. 2-3: performance-model heatmaps (predicted TFLOPS over the
(memory bandwidth x int8 throughput) plane at m=n=k=16384, c=N).

Printed as CSV rows (one per bandwidth) so the heatmap can be re-plotted;
also reports the paper's GH200 spot check: ZGEMM accu ~120 TFLOPS at
b=2-4 TB/s, p=1500 TOPS.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.perfmodel import HW, complex_tflops

from .common import emit


def run(size: int = 16384):
    bws = np.linspace(0.5e12, 8e12, 6)
    opss = np.linspace(250e12, 4500e12, 6)
    for prec, nm in (("c", 6), ("z", 13)):
        for mode in ("fast", "accu"):
            for b in bws:
                row = []
                for p in opss:
                    hw = HW("grid", b, p, 0, 0)
                    row.append(complex_tflops(size, size, size, nm, hw, mode, prec, c=nm))
                emit(
                    f"fig23/{prec}gemm/{mode}-{nm}/bw{b/1e12:.1f}TBs",
                    0.0,
                    "tflops_vs_ops=" + "/".join(f"{t:.0f}" for t in row),
                )
    spot = complex_tflops(
        size, size, size, 13, HW("gh200-spot", 3e12, 1500e12, 0, 0), "accu", "z", c=13
    )
    emit("fig23/spotcheck/gh200_zgemm_accu", 0.0,
         f"tflops={spot:.0f};paper_prediction~120")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
