"""One-shot on-device calibration microbenchmark -> measured `HW`.

Measures, on whatever backend this process actually runs on:

* sustained int8-dot MAC rate (`HW.int8_ops` — ops counted as mul+add, the
  SIII-C model's `p`), via a jitted int8 x int8 -> int32 `dot_general`;
* sustained fp8 (e4m3) dot rate (`HW.fp8_ops`), when the backend supports
  e4m3 matmuls — 0.0 otherwise, which the model reads as "no native fp8";
* memory bandwidth (`HW.mem_bw`), via a streaming read+write over an array
  far larger than cache;
* per-`pallas_call` launch overhead (`HW.gemm_launch_s`), via a tiny Pallas
  copy kernel whose compute is negligible — wall time IS the dispatch cost
  (in interpret mode off-TPU this is large, and that is the truth the model
  should price launches at on this host);
* native complex GEMM rates (`HW.native_c64` / `native_c128`) for the
  speedup-over-native comparisons (0.0 where the dtype is unsupported);
* per-device psum bandwidth + collective launch overhead (`HW.ici_bw` /
  `HW.collective_launch_s`) when >1 device is visible — single-device hosts
  keep the presets (there is nothing to measure).

`calibrate()` bundles the measurements with the `repro.tune.autotune` block
winners into a `Calibration` ready for `save_calibration`.  Smoke mode
shrinks every probe so the whole calibration finishes in seconds on a CPU
CI host; the measured numbers are then noisy but structurally valid — and
by design calibration can only ever change *speed*, never numerics.
"""
from __future__ import annotations

import time

from .cache import Calibration, live_key

# probe sizes: (smoke, full)
_MEM_ELEMS = (1 << 20, 1 << 24)       # f32 elements of the bandwidth probe
_DOT_DIM = (256, 1024)                # square dim of the engine-rate probes
_NATIVE_DIM = (128, 512)
_PSUM_ELEMS = (1 << 16, 1 << 22)      # per-device f32 elements


def _time_s(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall-seconds per call of a jitted fn (blocks on the result)."""
    import jax
    import numpy as np

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _measure_mem_bw(smoke: bool) -> float:
    import jax
    import jax.numpy as jnp

    n = _MEM_ELEMS[0] if smoke else _MEM_ELEMS[1]
    x = jnp.arange(n, dtype=jnp.float32)
    f = jax.jit(lambda v: v * 1.000001 + 1.0)
    t = _time_s(f, x)
    return 2.0 * 4.0 * n / t  # one read + one write of 4-byte elements


def _unsupported_errors() -> tuple:
    import jax

    # a dtype or op this backend does not compile (e.g. c128 on a TPU)
    return (jax.errors.JaxRuntimeError, NotImplementedError, TypeError)


def _unsupported(what: str, err: Exception) -> float:
    """Record a probe the backend cannot run as rate 0.0, and say so."""
    import warnings

    warnings.warn(f"calibration: {what} is unsupported here ({err}); "
                  "recording rate 0.0", stacklevel=3)
    return 0.0


def _measure_int8_ops(smoke: bool) -> float:
    import jax
    import jax.lax as lax
    import jax.numpy as jnp
    import numpy as np

    d = _DOT_DIM[0] if smoke else _DOT_DIM[1]
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(-63, 64, (d, d), dtype=np.int8))
    b = jnp.asarray(rng.integers(-63, 64, (d, d), dtype=np.int8))
    f = jax.jit(
        lambda x, w: lax.dot_general(
            x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
        )
    )
    t = _time_s(f, a, b)
    return 2.0 * d**3 / t


def _measure_fp8_ops(smoke: bool) -> float:
    """e4m3 dot rate, 0.0 when the backend cannot run one at all."""
    import jax
    import jax.lax as lax
    import jax.numpy as jnp
    import numpy as np

    d = _DOT_DIM[0] if smoke else _DOT_DIM[1]
    try:
        e4m3 = jnp.float8_e4m3fn
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.integers(-7, 8, (d, d)), jnp.float32).astype(e4m3)
        b = jnp.asarray(rng.integers(-7, 8, (d, d)), jnp.float32).astype(e4m3)
        f = jax.jit(
            lambda x, w: lax.dot_general(
                x, w, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        )
        t = _time_s(f, a, b)
        return 2.0 * d**3 / t
    except _unsupported_errors() as e:
        return _unsupported("e4m3 dot", e)


def _measure_native_rate(dtype_name: str, smoke: bool) -> float:
    """Native complex GEMM flop rate (8 m n k flops), 0.0 if unsupported."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    d = _NATIVE_DIM[0] if smoke else _NATIVE_DIM[1]
    try:
        dt = jnp.dtype(dtype_name)
        rng = np.random.default_rng(0)
        a = jnp.asarray(
            (rng.standard_normal((d, d)) + 1j * rng.standard_normal((d, d)))
        ).astype(dt)
        f = jax.jit(jnp.matmul)
        t = _time_s(f, a, a)
        return 8.0 * d**3 / t
    except _unsupported_errors() as e:
        return _unsupported(f"native {dtype_name} matmul", e)


def _measure_gemm_launch_s() -> float:
    """Wall time of a compute-free Pallas launch (the dispatch overhead)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from ..kernels.common import interpret_default

    def _copy(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    x = jnp.zeros((8, 128), jnp.float32)
    f = jax.jit(
        lambda v: pl.pallas_call(
            _copy,
            out_shape=jax.ShapeDtypeStruct(v.shape, v.dtype),
            interpret=interpret_default(),
            name="launch_probe",
        )(v)
    )
    return _time_s(f, x)


def _measure_psum(smoke: bool) -> tuple[float, float]:
    """(ici_bw B/s, collective_launch_s); (0, 0) on single-device hosts
    (meaning "not measured" — `HW.from_calibration` keeps the presets)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    d = jax.device_count()
    if d < 2:
        return 0.0, 0.0
    f = jax.pmap(lambda x: jax.lax.psum(x, "i"), axis_name="i")
    tiny = jnp.zeros((d, 8), jnp.float32)
    t_tiny = _time_s(f, tiny)
    n = _PSUM_ELEMS[0] if smoke else _PSUM_ELEMS[1]
    big = jnp.asarray(
        np.random.default_rng(0).standard_normal((d, n)), jnp.float32
    )
    t_big = _time_s(f, big)
    # ring all-reduce moves ~2(d-1)/d of the payload per device
    byts = 2.0 * (d - 1) / d * 4.0 * n
    bw = byts / max(t_big - t_tiny, 1e-9)
    return bw, t_tiny


def measure_hw(smoke: bool = False) -> dict:
    """Run every microbenchmark; returns the `HW.from_calibration` dict."""
    ici_bw, coll_s = _measure_psum(smoke)
    return {
        "mem_bw": _measure_mem_bw(smoke),
        "int8_ops": _measure_int8_ops(smoke),
        "fp8_ops": _measure_fp8_ops(smoke),
        "native_c64": _measure_native_rate("complex64", smoke),
        "native_c128": _measure_native_rate("complex128", smoke),
        "gemm_launch_s": _measure_gemm_launch_s(),
        "ici_bw": ici_bw,
        "collective_launch_s": coll_s,
    }


def calibrate(
    smoke: bool = False, *, blocks: bool = True, verbose: bool = False
) -> Calibration:
    """The one-shot calibration: microbench + (optionally) block autotune.

    Returns a `Calibration` for the live backend, ready to persist with
    `save_calibration` and activate with `set_calibration` /
    `use_calibration`.  `blocks=False` skips the autotuner (HW only).
    """
    from ..core.perfmodel import HW

    key = live_key()
    meas = measure_hw(smoke)
    if verbose:
        for k in sorted(meas):
            print(f"  measured {k:>20s} = {meas[k]:.3e}")
    hw = HW.from_calibration(meas, name=f"calibrated/{key['device_kind']}")
    cal = Calibration(hw=hw, **key)
    if blocks:
        from .autotune import autotune_blocks

        cal = cal.with_blocks(autotune_blocks(smoke=smoke, verbose=verbose))
    return cal
