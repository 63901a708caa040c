"""Chip smoke test: the emulated GEMMs and one full-width model on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # sharded zgemm on a 4-chip host

One chip: sgemm, dgemm, cgemm and zgemm at m = k = n = 8192 (fast mode,
default moduli counts) on the `kernel` and `fused` executions, then three
training steps of mamba2-130m at its published widths through
`repro.launch.train`.  Four chips: zgemm at 8192 under
`execution="sharded"` on a (1, 1, 4) residue mesh and a (2, 2) m/n mesh,
each bitwise against the one-device `kernel` result, and nothing else.

Everything runs in this one process.  Without a TPU the script exits
nonzero before any phase; any failed check raises.  The last line of
standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import functools
import json
import re
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SIZE = 8192
PHI = 0.5  # the paper's operand generator, SIV-A
SAMPLE_ROWS = 64
#: max|C - ref| / max|ref| band of the f64 routines on the Pallas paths,
#: which quantize through f32 (tests/test_linalg.py, execution parity)
F32_GRADE_BAND = 1e-6
#: step-1 loss of the emulated run against the native run, relative: the
#: native run computes in bf16, the emulated one in f32
LOSS_RTOL = 1e-2
FAMILIES = {
    "sgemm": np.float32,
    "dgemm": np.float64,
    "cgemm": np.complex64,
    "zgemm": np.complex128,
}


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(count: int):
    """The device JAX runs on, or exit: this smoke has no CPU fallback."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(
            f"chip_smoke: no TPU (JAX platform is {devs[0].platform!r}); "
            "run it on a TPU host"
        )
    if len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips, JAX sees {len(devs)}")
    return devs[0]


def operands(rng, dtype, n: int):
    from benchmarks.common import phi_matrix

    return phi_matrix(rng, (n, n), PHI, dtype), phi_matrix(rng, (n, n), PHI, dtype)


def device_args(family: str, a, b, put):
    """The device operands of one routine: zgemm runs on planar f64 parts."""
    if family == "zgemm":
        return tuple(put(np.ascontiguousarray(p)) for p in (a.real, a.imag, b.real, b.imag))
    return put(a), put(b)


def routine(family: str, policy):
    from repro import linalg

    if family == "zgemm":
        return functools.partial(linalg.zgemm_planar, policy=policy)
    return functools.partial(getattr(linalg, family), policy=policy)


def to_host(family: str, out) -> np.ndarray:
    if family == "zgemm":
        cr, ci = (np.asarray(p) for p in out)
        c = np.empty(cr.shape, np.complex128)
        c.real, c.imag = cr, ci
        return c
    return np.asarray(out)


class CacheEvents:
    """Counts of JAX's persistent compilation cache hits and misses."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._event)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def since(self, mark):
        """'h hits, m misses' since `mark`, a (hits, misses) pair."""
        return f"{self.hits - mark[0]} hits, {self.misses - mark[1]} misses"


def compile_all(jobs: dict, cache: CacheEvents) -> dict:
    """{key: (compiled, compile s)} of {key: (fn, arg shapes)}, each checked
    to hold a Pallas TPU kernel.  Lowering holds the interpreter lock, so
    the programs are lowered one by one, then compiled (or read from the
    persistent cache) concurrently."""
    import jax

    t0 = time.perf_counter()
    lowered = {key: jax.jit(fn).lower(*shapes) for key, (fn, shapes) in jobs.items()}
    t1 = time.perf_counter()
    mark = (cache.hits, cache.misses)

    def one(low):
        t = time.perf_counter()
        compiled = low.compile()
        return compiled, time.perf_counter() - t

    with ThreadPoolExecutor(max_workers=len(jobs)) as pool:
        done = dict(zip(lowered, pool.map(one, lowered.values())))
    log(f"lowered {len(jobs)} programs in {t1 - t0:.1f}s, compiled them "
        f"concurrently in {time.perf_counter() - t1:.1f}s; compile cache "
        f"{cache.since(mark)}")
    for key, (compiled, _) in done.items():
        if "tpu_custom_call" not in compiled.as_text():
            raise AssertionError(f"{key}: compiled program holds no TPU kernel")
    return done


def run_twice(compiled, args):
    """(output, first-run s, second-run s), each ending in block_until_ready."""
    import jax

    runs = []
    for _ in range(2):
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        runs.append(time.perf_counter() - t0)
    return out, runs[0], runs[1]


def accuracy(family, c, a, b, rows, n_moduli, formulation):
    """(metric name, error, limit) of the sampled rows of C."""
    from repro.core.accuracy import rel_bound, rel_error

    wide = np.complex128 if np.iscomplexobj(a) else np.float64
    ref = a[rows].astype(wide) @ b.astype(wide)
    if family in ("sgemm", "cgemm"):
        dtype = np.dtype(FAMILIES[family]).name
        err = rel_error(c[rows], ref, a[rows], b)
        lim = rel_bound(dtype, "fast", n_moduli, a.shape[1], formulation=formulation)
        return "rel_error/rel_bound", err, lim
    err = float(np.max(np.abs(c[rows] - ref)) / np.max(np.abs(ref)))
    return "maxnorm_rel/f32_band", err, F32_GRADE_BAND


def bitwise_equal(x: np.ndarray, y: np.ndarray) -> bool:
    return x.shape == y.shape and x.tobytes() == y.tobytes()


def arg_shapes(family: str, n: int, sharding):
    """The abstract device operands of one routine, placed by `sharding`."""
    import jax

    dtype = np.float64 if family == "zgemm" else FAMILIES[family]
    spec = jax.ShapeDtypeStruct((n, n), dtype, sharding=sharding)
    return (spec,) * (4 if family == "zgemm" else 2)


def gemm_phase(seed: int, cache: CacheEvents):
    """Every family on `kernel` and `fused` at SIZE^3, checked on the host."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from repro.core.plan import default_n_moduli
    from repro.core.policy import GemmPolicy

    one = SingleDeviceSharding(jax.devices()[0])
    policies = {
        ex: GemmPolicy(mode="fast", execution=ex) for ex in ("kernel", "fused")
    }
    programs = compile_all(
        {
            (family, ex): (routine(family, pol), arg_shapes(family, SIZE, one))
            for family in FAMILIES
            for ex, pol in policies.items()
        },
        cache,
    )
    rng = np.random.default_rng(seed)
    for family, dtype in FAMILIES.items():
        a, b = operands(rng, dtype, SIZE)
        args = device_args(family, a, b, jax.device_put)
        n_moduli = default_n_moduli(dtype, "fast")
        rows = np.sort(rng.choice(SIZE, size=SAMPLE_ROWS, replace=False))
        outs = {}
        for execution, policy in policies.items():
            compiled, comp_s = programs.pop((family, execution))
            out, first_s, run_s = run_twice(compiled, args)
            del compiled
            c = to_host(family, out)
            del out
            metric, err, lim = accuracy(
                family, c, a, b, rows, n_moduli, policy.formulation
            )
            log(
                f"gemm {family} {execution} n={SIZE} N={n_moduli}: compile "
                f"{comp_s:.2f}s first-run {first_s:.4f}s run {run_s:.4f}s "
                f"{metric} {err:.3e} <= {lim:.3e}"
            )
            if not np.all(np.isfinite(c)) or not err <= lim:
                raise AssertionError(f"{family}/{execution}: error {err} > {lim}")
            outs[execution] = c
        same = bitwise_equal(outs["kernel"], outs["fused"])
        log(f"gemm {family} kernel == fused bitwise: {same}")
        if not same:
            raise AssertionError(f"{family}: kernel and fused outputs differ")
        # the c128 boundary: zgemm itself, c128 in and out on the host
        if family == "zgemm":
            from repro import linalg

            small = 64
            kernel = GemmPolicy(execution="kernel")
            z = linalg.zgemm(a[:small, :small], b[:small, :small], policy=kernel)
            zp = to_host(
                "zgemm",
                jax.jit(routine("zgemm", kernel))(*device_args(
                    "zgemm", a[:small, :small], b[:small, :small], jax.device_put
                )),
            )
            ok = z.dtype == np.complex128 and bitwise_equal(z, zp)
            log(f"gemm zgemm c128 entry point == planar program: {ok}")
            if not ok:
                raise AssertionError("zgemm's c128 boundary changed the result")
        del args, outs


def model_phase(cache: CacheEvents, steps: int = 3):
    """mamba2-130m through `repro.launch.train`: emulated vs native.  The
    launcher draws its weights and batches from its own fixed seed."""
    from repro.launch import train

    base = ["--arch", "mamba2-130m", "--full", "--batch", "4", "--seq", "1024"]
    mark = (cache.hits, cache.misses)
    log("model native (1 step):")
    native = train.main(base + ["--steps", "1", "--backend", "native"])
    log(f"model ozaki2_f32 kernel ({steps} steps):")
    emu = train.main(
        base + ["--steps", str(steps), "--backend", "ozaki2_f32",
                "--execution", "kernel"]
    )
    rel = abs(emu[0] - native[0]) / abs(native[0])
    log(
        f"model losses {emu}; step 1 native {native[0]:.6f} emulated "
        f"{emu[0]:.6f} rel diff {rel:.3e} <= {LOSS_RTOL:.0e}"
    )
    if not all(np.isfinite(emu)) or not np.isfinite(native[0]):
        raise AssertionError(f"non-finite loss: {emu} / {native}")
    if not emu[-1] < emu[0]:
        raise AssertionError(f"loss did not decrease: {emu}")
    if not rel <= LOSS_RTOL:
        raise AssertionError(f"step-1 loss off native by {rel:.3e}")
    log(f"model phase compile cache {cache.since(mark)}")


def four_chip_phase(seed: int, cache: CacheEvents):
    """Sharded zgemm on a residue mesh and an m/n mesh vs one device."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    from repro.core.policy import GemmPolicy
    from repro.launch.mesh import make_mesh

    half = SIZE // 2
    # name: (mesh shape, mesh axes, (residue, m, n) GEMM axes, output shard)
    layouts = {
        "residue (1,1,4)": ((1, 1, 4), ("data", "model", "residue"),
                            ("residue", None, None), (SIZE, SIZE)),
        "m/n (2,2)": ((2, 2), ("data", "model"),
                      (None, "data", "model"), (half, half)),
    }
    one = SingleDeviceSharding(jax.devices()[0])
    placement = {}
    jobs = {"one device": (routine("zgemm", GemmPolicy(execution="kernel")),
                           arg_shapes("zgemm", SIZE, one))}
    for name, (shape, axes, gemm_axes, _) in layouts.items():
        mesh = make_mesh(shape, axes)
        placement[name] = NamedSharding(mesh, PartitionSpec())  # replicated
        policy = GemmPolicy(execution="sharded", mesh=mesh, shard_axes=gemm_axes)
        jobs[name] = (routine("zgemm", policy), arg_shapes("zgemm", SIZE, placement[name]))
    programs = compile_all(jobs, cache)

    a, b = operands(np.random.default_rng(seed), np.complex128, SIZE)
    compiled, comp_s = programs.pop("one device")
    out, _, run_s = run_twice(
        compiled, device_args("zgemm", a, b, lambda x: jax.device_put(x, one))
    )
    ref = to_host("zgemm", out)
    del out
    log(f"sharded zgemm n={SIZE} one device kernel: compile {comp_s:.2f}s "
        f"run {run_s:.4f}s")
    for name, (_, _, _, shard_want) in layouts.items():
        compiled, comp_s = programs.pop(name)
        args = device_args(
            "zgemm", a, b, lambda x: jax.device_put(x, placement[name])
        )
        out, _, run_s = run_twice(compiled, args)
        text = compiled.as_text()
        parts = re.search(r"num_partitions=(\d+)", text)
        parts = int(parts.group(1)) if parts else 1
        devices = sorted({d.id for p in out for d in p.sharding.device_set})
        shard = out[0].sharding.shard_shape(out[0].shape)
        same = bitwise_equal(to_host("zgemm", out), ref)
        log(
            f"sharded zgemm n={SIZE} {name}: compile {comp_s:.2f}s run "
            f"{run_s:.4f}s partitions {parts} output devices {devices} shard "
            f"{shard} all-reduce {'all-reduce' in text} == one-device "
            f"bitwise: {same}"
        )
        if not same:
            raise AssertionError(f"{name}: sharded zgemm differs from one device")
        if len(devices) != 4 or parts != 4 or shard != shard_want:
            raise AssertionError(f"{name}: work not spread over 4 devices")
        del out, args, compiled


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded zgemm on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = require_tpu(4 if args.four_chips else 1)
    try:
        import repro  # noqa: F401
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        sys.exit(f"chip_smoke: the repro package is not beside this script: {e}")
    import jax

    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {enable_compile_cache()}")
    cache = CacheEvents()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(args.seed, cache)
    else:
        gemm_phase(args.seed, cache)
        log(f"gemm phase {time.perf_counter() - t0:.1f}s")
        model_phase(cache)
    log(f"total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
