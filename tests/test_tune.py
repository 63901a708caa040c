"""repro.tune: calibration cache, scoping, tuned blocks, bench hygiene.

The contract under test (ISSUE 9 / docs/calibration.md):

* the cache round-trips exactly and *degrades, never breaks*: a stale,
  corrupt or missing file warns and falls back to the presets + static
  default blocks;
* with no calibration present, behaviour is bitwise identical to the
  pre-calibration code — presets price every 'auto' decision and the
  kernels launch their default blocks (the static ones, but the complex
  residue product's shape-and-VMEM tile);
* with a calibration active, the measured `HW` drives the 'auto'
  selections deterministically and the kernels launch the tuned blocks —
  which can never change numerics (pad-and-slice), only speed;
* bench_throughput's tracked-record merge dedupes and its --compare diff
  catches per-device-throughput regressions.
"""
import dataclasses
import json

import numpy as np
import jax.numpy as jnp
import pytest

import repro  # noqa: F401  (enables x64)
from repro.core import perfmodel
from repro.core.perfmodel import HW, TPU_V5E
from repro.core.policy import GemmPolicy
from repro.kernels.common import DEFAULT_GEMM_BLOCKS, resolve_blocks
from repro.tune.cache import (
    Calibration,
    block_key,
    calibration_hash,
    default_cache_path,
    live_key,
    load_calibration,
    save_calibration,
    set_calibration,
    shape_bucket,
    use_calibration,
)

from conftest import phi_matrix


def make_cal(blocks=None, **hw_over) -> Calibration:
    """A live-keyed calibration with a distinctive measured HW."""
    hw = dataclasses.replace(
        HW("calibrated/test", mem_bw=1e10, int8_ops=5e12, native_c64=0.0,
           native_c128=0.0, ici_bw=1e9, fp8_ops=0.0, gemm_launch_s=1e-4,
           collective_launch_s=3e-4),
        **hw_over,
    )
    return Calibration(**live_key(), hw=hw).with_blocks(blocks or {})


# --------------------------------------------------------------- the cache


def test_cache_roundtrip(tmp_path):
    cal = make_cal({
        block_key("kernel", "real", 256, 256, 512): (128, 128, 256),
        block_key("fused", "complex", 2048, 2048, 2048): (512, 512, 512),
    })
    path = save_calibration(cal, str(tmp_path / "cal.json"))
    loaded = load_calibration(path)
    assert loaded == cal
    assert hash(loaded) == hash(cal)  # frozen: rides in jit statics
    assert calibration_hash(loaded) == calibration_hash(cal)
    assert loaded.block_for("kernel/real/m256n256k512") == (128, 128, 256)
    assert loaded.block_for("kernel/real/m128n128k128") is None


def test_cache_stale_key_warns_and_falls_back(tmp_path):
    cal = make_cal()
    path = str(tmp_path / "cal.json")
    save_calibration(cal, path)
    obj = json.load(open(path))
    obj["key"]["device_count"] += 7  # measured on a different machine
    json.dump(obj, open(path, "w"))
    with pytest.warns(RuntimeWarning, match="stale"):
        assert load_calibration(path) is None
    # staleness check is opt-out for offline inspection
    assert load_calibration(path, check_staleness=False) is not None


@pytest.mark.parametrize("payload", [
    "definitely not json {",
    json.dumps({"schema": 1}),                      # missing key/hw
    json.dumps({"schema": 99, "key": {}, "hw": {}}),  # wrong schema
])
def test_cache_corruption_warns_and_falls_back(tmp_path, payload):
    path = tmp_path / "cal.json"
    path.write_text(payload)
    with pytest.warns(RuntimeWarning, match="unreadable"):
        assert load_calibration(str(path)) is None


def test_cache_malformed_blocks_rejected(tmp_path):
    cal = make_cal()
    path = str(tmp_path / "cal.json")
    save_calibration(cal, path)
    obj = json.load(open(path))
    obj["blocks"] = {"kernel/real/m128n128k128": [256, -1, 0]}
    json.dump(obj, open(path, "w"))
    with pytest.warns(RuntimeWarning, match="unreadable"):
        assert load_calibration(path) is None


def test_cache_missing_file_warns_none(tmp_path):
    with pytest.warns(RuntimeWarning, match="unreadable"):
        assert load_calibration(str(tmp_path / "nope.json")) is None


def test_default_cache_path_respects_env(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CALIBRATION_DIR", str(tmp_path))
    p = default_cache_path()
    assert p.startswith(str(tmp_path))
    assert p.endswith(".json")


def test_shape_bucketing():
    assert shape_bucket(1, 1, 1) == "m128n128k128"       # floor: MXU tile
    assert shape_bucket(129, 256, 300) == "m256n256k512"  # round up pow2
    assert shape_bucket(10**6, 1, 1).startswith("m16384")  # cap
    with pytest.raises(ValueError):
        block_key("nope", "real", 1, 1, 1)
    with pytest.raises(ValueError):
        block_key("kernel", "int8", 1, 1, 1)


# ------------------------------------------------------------------ scoping


def test_scoping_thread_local_beats_global():
    from repro.tune.cache import current_calibration

    a, b = make_cal(), make_cal(mem_bw=2e10)
    assert current_calibration() is None
    try:
        set_calibration(a)
        assert current_calibration() == a
        with use_calibration(b):
            assert current_calibration() == b  # innermost wins
        assert current_calibration() == a
    finally:
        set_calibration(None)
    assert current_calibration() is None


def test_use_calibration_from_unfit_path_is_noop(tmp_path):
    from repro.tune.cache import current_calibration

    bad = tmp_path / "bad.json"
    bad.write_text("{")
    with pytest.warns(RuntimeWarning):
        with use_calibration(str(bad)):
            assert current_calibration() is None  # degraded, not broken


# ------------------------------------- measured HW drives 'auto' decisions


def test_default_hw_is_preset_without_calibration():
    assert perfmodel.default_hw() is TPU_V5E


def test_default_hw_follows_active_calibration():
    cal = make_cal()
    with use_calibration(cal):
        assert perfmodel.default_hw() == cal.hw
    assert perfmodel.default_hw() is TPU_V5E


def test_calibrated_hw_flips_engine_auto_selection():
    """An fp8-rich measured HW flips select_engine — the smoke proof that
    'auto' decisions really price against the measurement, not the preset."""
    shape = (4096, 4096, 4096, 14)
    assert perfmodel.select_engine(*shape) == "int8"  # v5e has no fp8 MXU
    fp8_rich = make_cal(fp8_ops=100 * 5e12)
    with use_calibration(fp8_rich):
        assert perfmodel.select_engine(*shape) == "fp8"
    assert perfmodel.select_engine(*shape) == "int8"


def test_pinned_policy_calibration_is_deterministic(tmp_path):
    """GemmPolicy(calibration=path): same plan on every call, identical to
    the plan under an ambient use_calibration of the same cache — and the
    pin beats a different ambient calibration (no scope leakage into the
    jit-static plan)."""
    cal = make_cal(mem_bw=1e9, gemm_launch_s=5e-3)  # launch-dominated
    path = save_calibration(cal, str(tmp_path / "cal.json"))
    base = dict(backend="ozaki2_c64", n_moduli=5, formulation="auto",
                n_block="auto")
    pinned = GemmPolicy(calibration=path, **base)
    plan1 = pinned.plan_for(96, 96, 96)
    plan2 = pinned.plan_for(96, 96, 96)
    assert plan1 == plan2
    with use_calibration(cal):
        ambient_plan = GemmPolicy(**base).plan_for(96, 96, 96)
    assert plan1 == ambient_plan
    other = make_cal(mem_bw=9e14, int8_ops=9e15, gemm_launch_s=1e-9)
    with use_calibration(other):
        assert pinned.plan_for(96, 96, 96) == plan1


def test_policy_pinned_unfit_cache_degrades(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("broken")
    with pytest.warns(RuntimeWarning):
        pol = GemmPolicy(backend="ozaki2_c64", n_moduli=5,
                         formulation="auto", calibration=str(bad))
        plan = pol.plan_for(64, 64, 64)
    ref = GemmPolicy(backend="ozaki2_c64", n_moduli=5,
                     formulation="auto").plan_for(64, 64, 64)
    assert plan == ref  # unfit pin == no pin == presets


# ----------------------------------------- tuned blocks: resolution + parity


def test_resolve_blocks_defaults_without_calibration():
    assert resolve_blocks("kernel", "real", 300, 300, 300) == \
        DEFAULT_GEMM_BLOCKS


def test_resolve_blocks_reads_tuned_and_respects_overrides():
    key = block_key("kernel", "real", 300, 300, 300)
    cal = make_cal({key: (128, 128, 256)})
    with use_calibration(cal):
        assert resolve_blocks("kernel", "real", 300, 300, 300) == \
            (128, 128, 256)
        # explicit per-axis args always beat the tuned winner
        assert resolve_blocks("kernel", "real", 300, 300, 300, bm=64) == \
            (64, 128, 256)
        assert resolve_blocks(
            "kernel", "real", 300, 300, 300, bm=1, bn=2, bk=3
        ) == (1, 2, 3)
        # a slot the cache does not cover falls back to the static default
        assert resolve_blocks("fused", "real", 300, 300, 300) == \
            DEFAULT_GEMM_BLOCKS
    assert resolve_blocks("kernel", "real", 300, 300, 300) == \
        DEFAULT_GEMM_BLOCKS


@pytest.mark.parametrize("size,tile,steps", [
    (8192, (1024, 1024, 1024), 7 * 8 * 8 * 8),
    (1024, (256, 256, 1024), 7 * 4 * 4),
    (300, (128, 128, 300), 7 * 3 * 3),
])
def test_resolve_blocks_complex_kernel_takes_the_product_tile(size, tile, steps):
    """No calibration, no explicit block: the complex residue product runs
    the tile of `product_tile` (bm, bn at most a quarter of their axis),
    shrunk to a small or ragged axis, at the grid PERF.md section 4
    records, inside its VMEM budget."""
    from repro.kernels.common import (
        PRODUCT_VMEM_BUDGET,
        block_and_padded,
        product_vmem_bytes,
    )

    got = resolve_blocks("kernel", "complex", size, size, size)
    assert got == tile
    padded = [block_and_padded(size, b, align=128) for b in got]
    assert [b for b, _ in padded] == list(tile)  # the kernel runs it as is
    assert 7 * np.prod([p // b for b, p in padded]) == steps
    assert product_vmem_bytes(*tile) <= PRODUCT_VMEM_BUDGET


@pytest.mark.parametrize("family,dclass", [
    ("kernel", "real"), ("fused", "real"), ("fused", "complex"),
    ("fp8", "real"), ("fp8", "complex"),
])
@pytest.mark.parametrize("size", [8192, 1024])
def test_resolve_blocks_other_kernels_keep_the_static_default(family, dclass, size):
    assert resolve_blocks(family, dclass, size, size, size) == \
        DEFAULT_GEMM_BLOCKS


def test_resolve_blocks_complex_kernel_overrides_beat_the_product_tile():
    """Explicit axes and an active calibration still win over the rule."""
    assert resolve_blocks("kernel", "complex", 8192, 8192, 8192, bm=256) == \
        (256, 1024, 1024)
    assert resolve_blocks(
        "kernel", "complex", 8192, 8192, 8192, bm=256, bn=256, bk=512
    ) == DEFAULT_GEMM_BLOCKS
    cal = make_cal({block_key("kernel", "complex", 8192, 8192, 8192):
                    (128, 128, 256)})
    with use_calibration(cal):
        assert resolve_blocks("kernel", "complex", 8192, 8192, 8192) == \
            (128, 128, 256)
        assert resolve_blocks("kernel", "complex", 8192, 8192, 8192,
                              bk=512) == (128, 128, 512)


@pytest.mark.parametrize("family,dclass", [("kernel", "complex"), ("fused", "complex")])
def test_autotune_times_each_slot_at_its_default(monkeypatch, family, dclass):
    """The autotuner's candidates hold the slot's default: the product
    tile for the complex residue product only, the static one for all."""
    from repro.kernels.common import product_tile
    from repro.tune import autotune

    timed = []
    monkeypatch.setattr(autotune, "_make_entry", lambda *a: lambda *tile: tile)
    monkeypatch.setattr(autotune, "_median_time_s",
                        lambda tile, iters: timed.append(tile) or 1.0)
    shape = (2048, 2048, 2048)
    autotune.autotune_blocks(families=(family,), dclasses=(dclass,), shapes=(shape,))
    assert DEFAULT_GEMM_BLOCKS in timed
    assert (product_tile(*shape) in timed) == (family == "kernel")


def test_no_calibration_kernel_blocks_are_the_static_defaults(rng):
    """No cache present => the batched kernel runs exactly the static
    default blocks: bitwise identity against an explicit (256, 256, 512)
    call (the pre-calibration behaviour)."""
    from repro.core.moduli import make_crt_context
    from repro.kernels.int8_mod_gemm import int8_mod_gemm_batched

    ctx = make_crt_context(5)
    a = jnp.asarray(rng.integers(-60, 61, (5, 40, 72), dtype=np.int8))
    b = jnp.asarray(rng.integers(-60, 61, (5, 72, 56), dtype=np.int8))
    y_auto = int8_mod_gemm_batched(a, b, moduli=ctx.moduli, interpret=True)
    y_static = int8_mod_gemm_batched(
        a, b, moduli=ctx.moduli, bm=256, bn=256, bk=512, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(y_auto), np.asarray(y_static))


def test_tuned_blocks_never_change_numerics_kernel(rng):
    """Pad-and-slice: a tuned block shape on a non-divisible shape is
    bitwise identical to the default — the autotuner only trades speed."""
    from repro.core.moduli import make_crt_context
    from repro.kernels.int8_mod_gemm import int8_mod_gemm_batched

    ctx = make_crt_context(5)
    m, k, n = 40, 72, 56  # nothing divides the 32-tile evenly
    a = jnp.asarray(rng.integers(-60, 61, (5, m, k), dtype=np.int8))
    b = jnp.asarray(rng.integers(-60, 61, (5, k, n), dtype=np.int8))
    y_default = int8_mod_gemm_batched(a, b, moduli=ctx.moduli,
                                      interpret=True)
    cal = make_cal({block_key("kernel", "real", m, n, k): (32, 32, 32)})
    with use_calibration(cal):
        assert resolve_blocks("kernel", "real", m, n, k) == (32, 32, 32)
        y_tuned = int8_mod_gemm_batched(a, b, moduli=ctx.moduli,
                                        interpret=True)
    np.testing.assert_array_equal(np.asarray(y_default), np.asarray(y_tuned))


def test_tuned_blocks_never_change_numerics_fused(rng):
    from repro.core.moduli import make_crt_context
    from repro.core.plan import n_limbs_for_ctx
    from repro.kernels.int8_mod_gemm import fused_mod_gemm

    ctx = make_crt_context(4)
    n_limbs = n_limbs_for_ctx(ctx)
    m, k, n = 40, 72, 56
    a = jnp.asarray(rng.integers(-500, 501, (m, k)), jnp.float32)
    b = jnp.asarray(rng.integers(-500, 501, (k, n)), jnp.float32)
    e_mu = jnp.zeros((m,), jnp.int32)
    e_nu = jnp.zeros((n,), jnp.int32)
    y_default = fused_mod_gemm(a, b, e_mu, e_nu, ctx, n_limbs=n_limbs,
                               interpret=True)
    cal = make_cal({block_key("fused", "real", m, n, k): (32, 32, 32)})
    with use_calibration(cal):
        y_tuned = fused_mod_gemm(a, b, e_mu, e_nu, ctx, n_limbs=n_limbs,
                                 interpret=True)
    np.testing.assert_array_equal(np.asarray(y_default), np.asarray(y_tuned))


def test_tuned_blocks_bitwise_through_the_policy_route(rng):
    """End to end: linalg.matmul on the kernel execution under a tuned
    calibration scope == the same matmul with no calibration, bitwise."""
    from repro import linalg

    m, k, n = 40, 72, 56
    a = jnp.asarray(phi_matrix(rng, (m, k), 0.5, np.float32))
    b = jnp.asarray(phi_matrix(rng, (k, n), 0.5, np.float32))
    pol = GemmPolicy(backend="ozaki2_f32", n_moduli=5, execution="kernel",
                     interpret=True)
    y_default = linalg.matmul(a, b, policy=pol)
    cal = make_cal({
        block_key("kernel", "real", m, n, k): (32, 32, 32),
        block_key("fused", "real", m, n, k): (32, 32, 32),
    })
    with use_calibration(cal):
        y_tuned = linalg.matmul(a, b, policy=pol)
    np.testing.assert_array_equal(np.asarray(y_default), np.asarray(y_tuned))


# ------------------------------------------- bench record hygiene + compare


def _rec(name="sgemm/fast/48", execution="kernel", mesh="1", devices=1,
         tflops=1.0, calibration=None):
    return {
        "name": name, "execution": execution, "mesh": mesh,
        "devices": devices, "us_per_call": 10.0,
        "tflops_aggregate": tflops * devices,
        "tflops_per_device": tflops, "calibration": calibration,
    }


def test_bench_merge_replaces_rekeys_and_dedupes():
    from benchmarks.bench_throughput import merge_records, record_key

    old = [
        _rec(tflops=1.0),             # duplicate pair: same key twice —
        _rec(tflops=2.0),             # the later record must win the dedupe
        _rec(execution="fused", tflops=3.0),
    ]
    new = [_rec(tflops=9.0)]
    merged = merge_records(old, new)
    keys = [record_key(r) for r in merged]
    assert len(keys) == len(set(keys)) == 2  # deduped + replaced
    by_key = {record_key(r): r for r in merged}
    assert by_key[record_key(new[0])]["tflops_per_device"] == 9.0
    assert by_key[record_key(old[2])]["tflops_per_device"] == 3.0


def test_bench_merge_calibration_stamp_separates_trajectories():
    from benchmarks.bench_throughput import merge_records

    old = [_rec(tflops=1.0, calibration=None)]
    new = [_rec(tflops=2.0, calibration="abc123def456")]
    merged = merge_records(old, new)
    assert len(merged) == 2  # tuned run never clobbers the untuned baseline


def test_bench_merge_refuses_unkeyed_without_force():
    from benchmarks.bench_throughput import merge_records

    old = [{"legacy": True}]
    with pytest.raises(SystemExit):
        merge_records(old, [_rec()])
    assert merge_records(old, [_rec()], force=True) == [_rec()]


def test_bench_compare_flags_only_real_regressions():
    from benchmarks.bench_throughput import compare_records

    baseline = [
        _rec(tflops=0.8),
        _rec(tflops=1.0),  # duplicate: the baseline bar is the max
        _rec(execution="fused", tflops=2.0),
    ]
    ok = [_rec(tflops=0.9)]  # -10%: inside the 15% tolerance
    assert compare_records(ok, baseline) == []
    slow = [_rec(tflops=0.5)]  # -50%: regression
    out = compare_records(slow, baseline)
    assert len(out) == 1 and "0.5" in out[0]
    # tuned records are held to the untuned bar (stamp ignored in matching)
    tuned_slow = [_rec(tflops=0.5, calibration="abc123def456")]
    assert len(compare_records(tuned_slow, baseline)) == 1
    # configs absent from the baseline are new coverage, not regressions
    novel = [_rec(execution="fp8", tflops=0.001)]
    assert compare_records(novel, baseline) == []
    # tolerance is a knob
    assert compare_records(ok, baseline, tolerance=0.01) != []
