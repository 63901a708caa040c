"""Sweep the tile of the fused Karatsuba residue-product kernel on a TPU.

    python experiments/karatsuba_tile_sweep.py [--sizes 8192 1024]

Times `karatsuba_mod_gemm_batched` alone at N = 7 moduli (the fast
complex64 plan) on random int8 residue planes, m = n = k = size, over
explicit tiles (bm, bn) in {256, 512, 1024}^2 (square and 2:1) and bk in
{512, 1024, 2048}, and at the tile the kernel resolves by default.  Each
tile is compiled (eight at a time; a tile the chip's compiler refuses,
as for VMEM, is recorded with its error), warmed up, timed on the host
clock over back-to-back calls, and then traced: the device ms per call is
the sum of the `karatsuba_mod_gemm` events on the device's "XLA Ops" line
over the traced calls.  Every tile's residues are checked bitwise against those of
the (256, 256, 512) tile.

Needs a TPU; prints one JSON line per tile and writes them all to
`--out`.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

N_MODULI = 7
BASE = (256, 256, 512)
PAIRS = ((256, 256), (512, 256), (256, 512), (512, 512), (1024, 512),
         (512, 1024), (1024, 1024))
BKS = (512, 1024, 2048)
#: (host-timed calls, traced calls) per size
CALLS = {8192: (6, 3), 1024: (300, 100)}


def device_ms(trace_dir: str, calls: int) -> tuple[float, int]:
    """(device ms per call of the product kernel, its event count) from the
    newest profile under `trace_dir`."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    data = ProfileData.from_file(str(files[-1]))
    planes = [p for p in data.planes if p.name.startswith("/device:TPU:")]
    plane = min(planes, key=lambda p: p.name)
    total_ns, events = 0, 0
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            if "karatsuba_mod_gemm" in ev.name:
                total_ns += ev.duration_ns
                events += 1
    return 1e-6 * total_ns / calls, events


def compile_tile(lowered):
    """(compiled program, compile s, None) of one lowered tile, or (None,
    None, the compiler's error)."""
    t0 = time.perf_counter()
    try:
        compiled = lowered.compile()
    except Exception as e:  # noqa: BLE001 - record what the compiler refused
        msg = str(e)
        j = msg.find("Scoped allocation")
        return None, None, msg[j:j + 120] if j >= 0 else msg.splitlines()[0][:300]
    return compiled, time.perf_counter() - t0, None


def time_tile(size: int, compiled, planes, base_out) -> tuple[dict, object]:
    """(timings, residues) of one compiled tile."""
    import jax
    import jax.numpy as jnp

    rec = {}
    out = jax.block_until_ready(compiled(*planes))
    jax.block_until_ready(compiled(*planes))
    if base_out is not None:
        rec["bitwise_vs_base"] = all(
            bool(jnp.array_equal(x, y)) for x, y in zip(out, base_out))
    n_host, n_trace = CALLS[size]
    t0 = time.perf_counter()
    for _ in range(n_host):
        out = compiled(*planes)
    jax.block_until_ready(out)
    rec["host_ms"] = 1e3 * (time.perf_counter() - t0) / n_host
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(n_trace):
                out = compiled(*planes)
            jax.block_until_ready(out)
        rec["device_ms"], rec["events"] = device_ms(d, n_trace)
    return rec, out


def run_tile(size: int, tile) -> dict:
    """The tile the kernel runs at m = n = k = size, and its grid steps."""
    from repro.kernels.common import block_and_padded, resolve_blocks

    blocks = tile or resolve_blocks("kernel", "complex", size, size, size)
    run, steps = [], N_MODULI
    for b in blocks:
        b, padded = block_and_padded(size, b, align=128)
        run.append(b)
        steps *= padded // b
    return {"run_tile": run, "grid_steps": steps}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--sizes", type=int, nargs="+", default=[8192, 1024])
    ap.add_argument("--out", default="karatsuba_tile_sweep.json")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    import repro  # noqa: F401  (x64, as every caller has it)
    from repro.core.moduli import make_crt_context
    from repro.kernels import karatsuba_mod_gemm_batched

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"karatsuba_tile_sweep: no TPU (platform {dev.platform!r})")
    ctx = make_crt_context(N_MODULI)
    moduli = tuple(int(p) for p in ctx.moduli)
    records = []
    for size in args.sizes:
        half = jnp.asarray([p // 2 for p in moduli], jnp.int32)[:, None, None]
        keys = jax.random.split(jax.random.key(size), 4)
        planes = [jax.device_put(jax.random.randint(
            key, (N_MODULI, size, size), -half, half + 1).astype(jnp.int8), dev)
            for key in keys]
        tiles = [BASE] + [(bm, bn, bk) for bm, bn in PAIRS for bk in BKS
                          if (bm, bn, bk) != BASE and bk <= size]
        # lowering holds the interpreter lock: lower one by one, then
        # compile concurrently
        lowered = [jax.jit(functools.partial(
            karatsuba_mod_gemm_batched, moduli=moduli, interpret=False,
            **({} if t is None else dict(zip(("bm", "bn", "bk"), t))),
        )).lower(*planes) for t in tiles + [None]]
        with ThreadPoolExecutor(max_workers=8) as pool:
            built = list(pool.map(compile_tile, lowered))
        base_out = None
        for tile, (compiled, compile_s, error) in zip(tiles + [None], built):
            rec = {"size": size, "tile": tile or "default",
                   **run_tile(size, tile)}
            if error is not None:
                rec["error"] = error
            else:
                rec["compile_s"] = compile_s
                timing, out = time_tile(size, compiled, planes, base_out)
                rec.update(timing)
                if tile == BASE:
                    base_out = out
            records.append(rec)
            print(json.dumps(rec), flush=True)
        del lowered, built, planes, base_out
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(
        {"device": dev.device_kind, "records": records}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
