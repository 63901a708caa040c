"""The reduction from a profiler trace to stage times, idle share and
roofline, against values computed by hand."""
from __future__ import annotations

import gzip
from pathlib import Path

import pytest

from bench import trace
from bench.metrics import idle_share, mfu, product_ms, product_roofline
from bench.work import Work

P = "bench."

# two calls; device ops (instruction text, start s, duration s) as the
# trace names them, on a device clock 0.25 s behind the host's
CAST = ('%_stacked_call.2 = s8[2,14,64,64]{3,2,1,0} custom-call(f32[2,64,64]{2,1,0} %p, '
        'f32[64,1]{1,0} %q, f32[64,1]{1,0} %r), custom_call_target="tpu_custom_call", '
        'operand_layout_constraints={f32[2,64,64]{2,1,0}, f32[64,1]{1,0}, f32[64,1]{1,0}}, '
        'frontend_attributes={kernel_metadata={}}')
PRODUCT = ('%_batched_call.1 = (s8[14,64,64]{2,1,0}, s8[14,64,64]{2,1,0}) custom-call('
           's32[14]{0} %m, s8[14,64,64]{2,1,0} %a, s8[14,64,64]{2,1,0} %b, s8[14,64,64]{2,1,0} %c, '
           's8[14,64,64]{2,1,0} %d), custom_call_target="tpu_custom_call", '
           'operand_layout_constraints={s32[14]{0}, s8[14,64,64]{2,1,0}, s8[14,64,64]{2,1,0}, '
           's8[14,64,64]{2,1,0}, s8[14,64,64]{2,1,0}}')
GARNER = ('%_unknown_.1 = f32[2,2,64,64]{3,2,1,0} custom-call(s8[2,14,64,64]{3,2,1,0} %e, '
          'f32[64,1]{1,0} %f, f32[64,1]{1,0} %g, f32[1,64]{1,0} %h, f32[1,64]{1,0} %i), '
          'custom_call_target="tpu_custom_call", operand_layout_constraints={s8[2,14,64,64]{3,2,1,0}, '
          'f32[64,1]{1,0}, f32[64,1]{1,0}, f32[1,64]{1,0}, f32[1,64]{1,0}}, '
          'frontend_attributes={kernel_metadata={}}')
COMBINE = ('%custom-call.9 = f64[64,64]{1,0} custom-call(f32[64,64]{1,0} %x, f32[64,64]{1,0} %y), '
           'custom_call_target="X64Combine"')
FUSION = "%fusion.1 = f32[64]{0} fusion(f32[64,64]{1,0} %z), kind=kLoop, calls=%fused_computation"
SKEW = 0.25
HOST = [
    ("bench.call", 10.0, 1.0), ("bench.dispatch", 10.0, 0.1), ("bench.block", 10.1, 0.9),
    ("bench.call", 11.5, 1.0), ("bench.dispatch", 11.5, 0.3), ("bench.block", 11.8, 0.7),
]
DEVICE = [(text, start + SKEW, dur) for text, start, dur in [
    (FUSION, 10.05, 0.95),
    (CAST, 11.0, 0.1),
    (PRODUCT, 11.9, 0.4),
    (GARNER, 12.3, 0.1),
    (COMBINE, 12.35, 0.1),        # overlaps Garner by 0.05 s
]]


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_events(DEVICE, HOST, P, SKEW)


def test_window_busy_and_calls(reduced):
    assert reduced.calls == 2
    assert reduced.window_s == pytest.approx(2.5)       # 10.0 .. 12.5
    # busy: [10.05, 11.1] + [11.9, 12.45] = 1.05 + 0.55
    assert reduced.busy_s == pytest.approx(1.6)


def test_stage_attribution(reduced):
    assert reduced.stage_s["cast"] == pytest.approx(0.1)
    assert reduced.stage_s["product"] == pytest.approx(0.4)
    assert reduced.stage_s["garner"] == pytest.approx(0.1)
    assert reduced.stage_s["outside"] == pytest.approx(0.95 + 0.1)
    assert reduced.per_call_ms("product") == pytest.approx(200.0)
    assert reduced.per_call_ms("kernel_other") is None


def test_idle_gaps_named_by_host_span(reduced):
    # gaps: [11.1, 11.9] (host in dispatch 11.5-11.8 for 0.3 s, 'call' only
    # otherwise; the widest overlap names it), [10.0, 10.05] in dispatch
    # and [12.45, 12.5] in block
    gaps = sorted(reduced.gaps, reverse=True)
    assert gaps[0] == (pytest.approx(0.8), "bench.dispatch")
    assert gaps[1] == (pytest.approx(0.05), "bench.dispatch")
    assert gaps[2] == (pytest.approx(0.05), "bench.block")
    bd = reduced.breakdown()
    assert bd["device_ops"][0] == ["%fusion.1 (outside)", pytest.approx(0.95)]
    assert len(bd["idle_gaps"]) == 3


class _Run:
    def __init__(self, t):
        self.trace = t
        self.work = Work(1024, 1024, 1024, 14, "complex128", "karatsuba")
        self.peaks = {"int8_ops": 393e12, "hbm_bytes_per_s": 819e9}


def test_metrics_from_the_reduction(reduced):
    run = _Run(reduced)
    assert idle_share.read(run) == pytest.approx(100 * (1 - 1.6 / 2.5))
    assert product_ms.read(run) == pytest.approx(200.0)
    # products: 6 * 14 * 1024^3 ops at 393e12 -> 2.2947e-4 s (the byte
    # bound, 14 * 2 * 3 * 1024^2 B at 819e9 -> 1.0755e-4 s, is lower)
    least = 6 * 14 * 1024**3 / 393e12
    assert product_roofline.read(run) == pytest.approx(100 * least / 0.2)
    assert mfu.read(run) == pytest.approx(100 * 2 * 8 * 1024**3 / 2.5 / 393e12)


def test_metrics_absent_without_kernels():
    t = trace.reduce_events([(FUSION, 10.0, 0.5)], HOST, P)
    run = _Run(t)
    assert product_ms.read(run) is None
    assert product_roofline.read(run) is None


def test_stage_of_each_instruction():
    assert [trace.stage_of_instruction(t) for t in (CAST, PRODUCT, GARNER, COMBINE, FUSION)] == [
        "cast", "product", "garner", "outside", "outside"]


def test_clock_offset_is_the_median_lag():
    modules = [1.30, 2.25, 3.26]
    launches = [1.0, 2.0, 3.0]
    assert trace.clock_offset(modules, launches) == pytest.approx(0.26)
    assert trace.clock_offset(modules, launches[:2]) == 0.0


# A trace recorded on a TPU v5 lite by this harness driving jitted
# `linalg.zgemm_planar` at m = n = k = 1024 (fast, N = 14, Karatsuba,
# kernel execution), 4 calls.  The expected
# values were computed by hand from the profiler's JSON export of the same
# trace (runsc.trace.json.gz, a separate file and format): window from the
# host's bench.call events, busy as the union of the XLA Ops events, and
# per-stage sums over the ops named _stacked_call.2/.3 (cast),
# _batched_call.1 (product) and _unknown_.1 (Garner) in that program.
RECORDED = Path(__file__).parent / "data" / "zgemm_fast_n14.sq1024.4calls.xplane.pb.gz"


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    data = ProfileData.from_serialized_xspace(gzip.decompress(RECORDED.read_bytes()))
    return trace.reduce_profile(data, P)


def test_recorded_trace_stages(recorded):
    assert recorded.calls == 4
    assert recorded.window_s == pytest.approx(26074.17e-6, rel=1e-6)
    # the xplane keeps whole nanoseconds and the JSON tenths of one: the
    # union's 2 x 224 op boundaries round by up to 0.5 ns each
    assert recorded.busy_s == pytest.approx(21811.181326e-6, abs=224e-9)
    assert recorded.per_call_ms("cast") == pytest.approx(1.9426612695, rel=1e-6)
    assert recorded.per_call_ms("product") == pytest.approx(1.835657578, rel=1e-6)
    assert recorded.per_call_ms("garner") == pytest.approx(1.4413931055, rel=1e-6)
    # 208 ops outside the kernels, each rounded by up to 0.5 ns
    assert recorded.per_call_ms("outside") == pytest.approx(0.2330833785, abs=208e-6 / 4)
    assert recorded.per_call_ms("kernel_other") is None


def test_recorded_trace_metrics(recorded):
    run = _Run(recorded)
    assert idle_share.read(run) == pytest.approx(
        100 * (1 - 21811.181326 / 26074.17), abs=100 * 224e-9 / 26074.17e-6)
    least = 6 * 14 * 1024**3 / 393e12          # ops-bound: 2.2951e-4 s
    assert product_roofline.read(run) == pytest.approx(100 * least / 1.835657578e-3, rel=1e-6)
    assert mfu.read(run) == pytest.approx(
        100 * 4 * 8 * 1024**3 / 26074.17e-6 / 393e12, rel=1e-6)
    gaps = recorded.breakdown()["idle_gaps"]
    assert [name for name, _ in gaps[:4]] == ["bench.block"] * 4
