"""Roofline analysis (deliverable g): derive the three roofline terms for
every (arch x shape) cell from the dry-run artifacts in experiments/dryrun.

    compute    = HLO_flops_per_device                  / peak_flops
    memory     = HLO_bytes_per_device                  / hbm_bw
    collective = collective_bytes_per_device           / ici_bw

TPU v5e constants: 197 TFLOP/s bf16 per chip (394 TOPS int8), 819 GB/s HBM,
~50 GB/s/link ICI.  flops/bytes use the loop-corrected values (the dry-run
lowers a scan-unrolled twin of each cell because XLA cost analysis counts
while-loop bodies once — EXPERIMENTS.md SDry-run).

Also reports MODEL_FLOPS (6*N_active*D for training, 2*N_active*D for
prefill/decode) and the MODEL/HLO ratio (recompute/overhead waste), the
dominant term, and a what-would-move-it suggestion per cell.
"""
from __future__ import annotations

import glob
import json
import os

from repro.configs import get_config
from repro.configs.shapes import SHAPES
from repro.models import Model
from repro.models.params import _iter_leaves

PEAK_BF16 = 197e12
PEAK_INT8 = 394e12
HBM_BW = 819e9
ICI_BW = 50e9


def active_params(cfg) -> tuple[int, int]:
    """(total, active) parameter counts from the abstract param tree."""
    model = Model(cfg)
    total = 0
    active = 0
    for path, meta in _iter_leaves(model.abstract_params()):
        import numpy as np

        n = int(np.prod(meta.shape))
        total += n
        if cfg.mlp == "moe" and len(path) >= 2 and path[-2] == "mlp" and path[-1] in (
            "gate",
            "up",
            "down",
        ):
            e = cfg.moe_experts
            n = n * cfg.moe_topk // e
        active += n
    return total, active


def model_flops(cfg, shape_name: str, n_chips: int) -> float:
    spec = SHAPES[shape_name]
    _, act = active_params(cfg)
    tokens = spec.global_batch * (spec.seq_len if spec.kind == "train" else 1)
    if spec.kind == "prefill":
        tokens = spec.global_batch * spec.seq_len
    factor = 6.0 if spec.kind == "train" else 2.0
    return factor * act * tokens / n_chips


def analyze(rec: dict) -> dict | None:
    if rec.get("status") != "ok":
        return None
    arch, shape = rec["arch"], rec["shape"]
    n_chips = 512 if len(rec["mesh"]) == 3 else 256
    peak = PEAK_INT8 if rec.get("backend", "native") != "native" else PEAK_BF16
    flops = rec.get("flops_per_device_corrected") or rec["flops_per_device"]
    bytes_ = rec.get("bytes_per_device_corrected") or rec["bytes_per_device"]
    coll = rec.get("collective_bytes_corrected") or rec["collectives"]["total"]
    t_c = flops / peak
    t_m = bytes_ / HBM_BW
    t_x = coll / ICI_BW
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_x), key=lambda x: x[1])
    mf = model_flops(get_config(arch), shape, n_chips)
    bound = t_c + t_m + t_x  # pessimistic no-overlap bound
    frac = (mf / peak) / max(bound, 1e-30)  # roofline fraction on useful flops
    hints = {
        "compute": "reduce recompute (remat policy) / fuse elementwise into the "
        "matmuls / int8 path doubles peak",
        "memory": "fuse or shrink intermediates (chunked-vocab CE, fused kernels), "
        "larger per-op tiles, bf16 intermediates",
        "collective": "reshard to cut all-gathers (SP/EP layout), overlap "
        "collectives with compute, gradient compression on DP axis",
    }
    return {
        "cell": rec["cell"],
        "arch": arch,
        "shape": shape,
        "mesh": "x".join(map(str, rec["mesh"])),
        "t_compute_s": t_c,
        "t_memory_s": t_m,
        "t_collective_s": t_x,
        "dominant": dom[0],
        "model_flops_per_dev": mf,
        "hlo_flops_per_dev": flops,
        "useful_ratio": mf / max(flops, 1e-30),
        "roofline_fraction": frac,
        "hint": hints[dom[0]],
        "mem_gib": rec["memory_analysis"].get("temp_size_in_bytes", 0) / 2**30,
        "backend": rec.get("backend", "native"),
        "tags": "+sp" * int(bool(rec.get("seq_shard"))) +
                (f"+ga{rec['grad_accum']}" if rec.get("grad_accum", 1) > 1 else ""),
    }


def load_all(dirname: str = "experiments/dryrun") -> list[dict]:
    rows = []
    for f in sorted(glob.glob(os.path.join(dirname, "*.json"))):
        with open(f) as fh:
            rec = json.load(fh)
        row = analyze(rec)
        if row:
            rows.append(row)
    return rows


def markdown_table(rows, single_pod_only=True) -> str:
    out = [
        "| cell | compute s | memory s | collective s | dominant | "
        "MODEL/HLO | roofline frac | temp GiB |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if single_pod_only and r["mesh"] != "16x16":
            continue
        out.append(
            f"| {r['cell']} | {r['t_compute_s']:.3e} | {r['t_memory_s']:.3e} | "
            f"{r['t_collective_s']:.3e} | {r['dominant']} | "
            f"{r['useful_ratio']:.2f} | {r['roofline_fraction']:.3f} | "
            f"{r['mem_gib']:.1f} |"
        )
    return "\n".join(out)


def run():
    rows = load_all()
    for r in rows:
        if r["mesh"] == "16x16":
            print(
                f"roofline/{r['cell']},0.0,"
                f"tc={r['t_compute_s']:.3e};tm={r['t_memory_s']:.3e};"
                f"tx={r['t_collective_s']:.3e};dom={r['dominant']};"
                f"frac={r['roofline_fraction']:.3f}"
            )
    os.makedirs("experiments", exist_ok=True)
    with open("experiments/roofline.md", "w") as f:
        f.write(markdown_table(rows) + "\n")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
