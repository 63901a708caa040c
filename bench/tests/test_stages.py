"""Device time per stage by the program's own names (bench/stages.py): the
map from compiled text to stages, the readers of `scale_ms`,
`assemble_ms` and `unstaged_ms`, and the recorded cgemm trace."""
from __future__ import annotations

import gzip
from pathlib import Path

import pytest

from bench import harness, stages, trace

TEXT = """HloModule jit_f, is_scheduled=true

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %neg = f32[4]{0} negate(%p), metadata={op_name="jit(f)/ozaki2.scale/neg"}
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/ozaki2.scale/neg"}
  %kernel.2 = s8[4]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/ozaki2.assemble/ozaki2.cast/jit(_stacked_call)/residue_cast/pallas_call"}
  %copy-start = (s32[7]{0}, s32[7]{0}, u32[]) copy-start(%x)
  ROOT %custom-call.4 = f32[4]{0} custom-call(%kernel.2), custom_call_target="X64SplitLow", metadata={op_name="x"}
}
"""


class _Run:
    def __init__(self, t):
        self.trace = t


def _trace(op_s, calls=2):
    return trace.Trace(1.0, 0.5, calls, {}, op_s, [])


def test_stage_map_takes_the_innermost_scope():
    names = stages.stage_map(TEXT)
    assert names["%fusion.1"] == "scale"
    assert names["%kernel.2"] == "cast"            # nested under assemble
    assert names["%copy-start"] == stages.UNSTAGED  # no metadata
    assert names["%custom-call.4"] == stages.UNSTAGED
    assert names["%neg"] == "scale"                  # inside the fusion
    assert len(names) == 7


def test_stage_seconds():
    names = stages.stage_map(TEXT)
    op_s = {("%fusion.1", "outside"): 0.25, ("%kernel.2", "cast"): 0.5,
            ("%custom-call.4", "outside"): 0.125}
    assert stages.stage_seconds(op_s, names) == {"scale": 0.25, "cast": 0.5,
                                                 stages.UNSTAGED: 0.125}
    assert stages.unstaged_ops(op_s, names) == {"%custom-call.4": 0.125}
    # an op the program's text does not hold: no guess
    assert stages.stage_seconds({**op_s, ("%other.9", "outside"): 1.0}, names) is None
    # a program that names no stage (one older than the scopes)
    bare = {name: stages.UNSTAGED for name in names}
    assert stages.stage_seconds(op_s, bare) is None


def test_metric_readers(monkeypatch):
    op_s = {("%fusion.1", "outside"): 0.25, ("%kernel.2", "cast"): 0.5,
            ("%custom-call.4", "outside"): 0.125}
    monkeypatch.setattr(stages, "live_program_text", lambda names: TEXT)
    run = _Run(_trace(op_s, calls=2))
    read = {m: harness.metric_module(m).read(run) for m in (
        "scale_ms", "assemble_ms", "unstaged_ms", "scale_ms.latency",
        "assemble_ms.latency", "unstaged_ms.latency")}
    assert read["scale_ms"] == read["scale_ms.latency"] == pytest.approx(125.0)
    assert read["assemble_ms"] == read["assemble_ms.latency"] == 0.0
    assert read["unstaged_ms"] == read["unstaged_ms.latency"] == pytest.approx(62.5)


def test_metric_readers_report_nothing_without_names(monkeypatch):
    op_s = {("%fusion.1", "outside"): 0.25}
    assert harness.metric_module("scale_ms").read(_Run(None)) is None
    monkeypatch.setattr(stages, "live_program_text", lambda names: None)
    assert harness.metric_module("unstaged_ms").read(_Run(_trace(op_s))) is None
    bare = TEXT.replace("ozaki2.", "")
    monkeypatch.setattr(stages, "live_program_text", lambda names: bare)
    assert harness.metric_module("unstaged_ms").read(_Run(_trace(op_s))) is None


def test_live_program_text_finds_the_program_that_ran():
    import functools
    import sys

    import jax
    import jax.numpy as jnp

    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro import linalg
    from repro.core.policy import GemmPolicy

    x = jnp.ones((8, 8), jnp.complex64)
    compiled = jax.jit(functools.partial(
        linalg.cgemm, policy=GemmPolicy(execution="kernel"))).lower(x, x).compile()
    text = compiled.as_text()
    names = list(stages.stage_map(text))
    assert stages.live_program_text(names) == text
    assert stages.live_program_text(names + ["%not-in-any-program.1"]) is None
    assert compiled is not None  # alive while it is looked up


# Four calls of jitted `linalg.cgemm` at m = n = k = 1024 (fast, N = 7,
# Karatsuba, kernel execution) traced on a TPU v5 lite by
# bench/record_trace.py, and the compiled program's text.
RECORDED = Path(__file__).parent / "data" / "cgemm_fast_n7.sq1024.4calls.xplane.pb.gz"
RECORDED_HLO = Path(__file__).parent / "data" / "cgemm_fast_n7.sq1024.hlo.txt.gz"


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    data = ProfileData.from_serialized_xspace(gzip.decompress(RECORDED.read_bytes()))
    names = stages.stage_map(gzip.decompress(RECORDED_HLO.read_bytes()).decode())
    return trace.reduce_profile(data, "bench."), names


def test_recorded_trace_every_op_has_one_stage(recorded):
    import sys

    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.core.executor import STAGES

    t, names = recorded
    assert t.calls == 4
    ops = {name for name, _ in t.op_s}
    assert len(ops) == 43 and ops <= set(names)
    assert {names[op] for op in ops} == {"scale", "cast", "product", "garner",
                                         "assemble", stages.UNSTAGED}
    assert {names[op] for op in ops} <= set(STAGES) | {stages.UNSTAGED}
    # the ops in no stage: the float32 halves of the two complex64
    # arguments and the async copy of the moduli constant
    assert sorted(stages.unstaged_ops(t.op_s, names)) == [
        "%copy-done", "%copy-start", "%custom-call", "%custom-call.1",
        "%custom-call.2", "%custom-call.3"]
    # each kernel by its pallas_call name, in its stage
    kernels = {op: names[op] for op, sig in t.op_s if sig != "outside"}
    assert kernels == {"%residue_cast.2": "cast", "%residue_cast.3": "cast",
                       "%karatsuba_mod_gemm.1": "product", "%crt_garner.1": "garner"}


def test_recorded_trace_named_totals(recorded):
    t, names = recorded
    by_name = stages.stage_seconds(t.op_s, names)
    # every op counted once, either way
    assert sum(by_name.values()) == pytest.approx(sum(t.stage_s.values()), abs=1e-12)
    # the product kernel alone is its stage; the cast and Garner stages
    # also hold the pads and slices around their kernels
    assert by_name["product"] == t.stage_s["product"]
    assert by_name["cast"] > t.stage_s["cast"] and by_name["garner"] > t.stage_s["garner"]
    assert by_name["cast"] - t.stage_s["cast"] + by_name["garner"] - t.stage_s["garner"] \
        + by_name["scale"] + by_name["assemble"] + by_name[stages.UNSTAGED] \
        == pytest.approx(t.stage_s["outside"], abs=1e-12)
    # per call, as the recorded trace's events sum (us)
    per_call_us = {k: 1e6 * v / t.calls for k, v in by_name.items()}
    assert per_call_us == pytest.approx({
        "scale": 82.68, "cast": 626.67, "product": 917.97, "garner": 436.48,
        "assemble": 12.90, stages.UNSTAGED: 24.57}, abs=0.01)
