"""The runtime's host events on the recorded zgemm trace (bench/runtime.py):
the idle split between programs and the causal clock bracket, against
values worked out by hand from the trace's events."""
from __future__ import annotations

import gzip
from pathlib import Path

import pytest

from bench import runtime, trace

P = "bench."
RECORDED = Path(__file__).parent / "data" / "zgemm_fast_n14.sq1024.4calls.xplane.pb.gz"
US = 1e-6


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData

    return ProfileData.from_serialized_xspace(gzip.decompress(RECORDED.read_bytes()))


@pytest.fixture(scope="module")
def programs(profile):
    return runtime.read_profile(profile)


def test_events_pair_with_the_programs(programs):
    assert len(programs.programs) == 4
    for events in (programs.launches, programs.enqueues, programs.dones, programs.blocks):
        assert len(events) == 4


def test_idle_split_by_hand(programs):
    # each gap, in us: device idle between programs (device clock); host,
    # from bench.block's end to the next PJRT_LoadedExecutable_Execute;
    # launch, from there to DoEnqueueProgram; sync, the rest
    by_hand = [(1233.2, 54.1, 483.4, 695.7),
               (1037.5, 40.7, 389.8, 607.1),
               (1005.4, 32.4, 438.0, 535.1)]
    gaps = runtime.idle_gaps(programs)
    assert len(gaps) == 3
    for g, (idle, host, launch, sync) in zip(gaps, by_hand):
        assert g["device_idle"] == pytest.approx(idle * US, abs=1 * US)
        assert g["host"] == pytest.approx(host * US, abs=1 * US)
        assert g["launch"] == pytest.approx(launch * US, abs=1 * US)
        assert g["sync"] == pytest.approx(sync * US, abs=1 * US)
    split = runtime.idle_split(programs)
    assert split["gaps"] == 3
    assert split["host"] + split["launch"] + split["sync"] == pytest.approx(
        split["device_idle"], abs=1e-12)
    assert split["host"] == pytest.approx((54.1 + 40.7 + 32.4) / 3 * US, abs=1 * US)


def test_idle_split_refuses_unpaired_events(programs):
    import dataclasses

    assert runtime.idle_gaps(dataclasses.replace(programs, enqueues=programs.enqueues[1:])) is None
    assert runtime.idle_split(dataclasses.replace(programs, blocks=programs.blocks[:3])) is None
    # pairs one to one but out of order: the second launch before the first block
    late = dataclasses.replace(programs, blocks=programs.blocks[1:] + programs.blocks[:1])
    assert runtime.idle_gaps(late) is None


def _maps_inside(programs, offset):
    """Each program, moved to the host clock by `offset` (device less host),
    starts after its enqueue and ends before the host sees it done."""
    return all(enq <= start - offset and end - offset <= done
               for (start, end), enq, done in zip(
                   programs.programs, programs.enqueues, programs.dones))


def test_clock_bracket(programs):
    lo, hi = runtime.clock_bracket(programs)
    # lo: the last program ends 1689.751 us before its Execute=>Done on
    # the device clock less the host's; hi: the second starts 1212.388 us
    # before its DoEnqueueProgram
    assert lo == pytest.approx(-1689.751 * US, abs=0.01 * US)
    assert hi == pytest.approx(-1212.388 * US, abs=0.01 * US)
    offset, how = runtime.clock_offset(programs)
    assert how == "bracket"
    assert lo < offset < hi
    assert offset == pytest.approx((-1689.751 - 1212.388) / 2 * US, abs=0.01 * US)
    assert _maps_inside(programs, offset)
    assert _maps_inside(programs, lo) and _maps_inside(programs, hi)


def test_the_median_launch_lag_breaks_causality(programs):
    # the median lag of each program's start after its launch: -750.2 us,
    # which puts every program about 0.45 ms before its enqueue
    old = trace.clock_offset([s for s, _ in programs.programs], programs.launches)
    assert old == pytest.approx(-750.211 * US, abs=0.01 * US)
    assert not _maps_inside(programs, old)
    import dataclasses

    missing = dataclasses.replace(programs, enqueues=[], dones=[])
    assert runtime.clock_bracket(missing) is None
    assert runtime.clock_offset(missing) == (
        old, "median launch lag (runtime enqueue/done events missing)")


def test_existing_values_read_the_same_on_the_bracket_clock(profile, programs):
    """Every per-layer value of bench/trace.py's reduction reads the same
    to the nanosecond whichever offset joins the clocks; only the idle
    gaps move: under the bracket the first call's launch (689 us) and the
    last call's return (296 us) show as gaps of their own."""
    ops, spans, old = trace.read_profile(profile, P)
    new, _ = runtime.clock_offset(programs)
    before = trace.reduce_events(ops, spans, P, old)
    after = trace.reduce_events(ops, spans, P, new)
    assert (after.calls, after.window_s) == (before.calls, before.window_s)
    assert after.busy_s == pytest.approx(before.busy_s, abs=1e-9)
    assert before.busy_s == pytest.approx(0.021811079, abs=1e-9)
    for stage, s in before.stage_s.items():
        assert after.stage_s[stage] == pytest.approx(s, abs=1e-9)
    for key, s in before.op_s.items():
        assert after.op_s[key] == pytest.approx(s, abs=1e-9)
    gaps = sorted(after.gaps, reverse=True)
    assert [round(s / US, 1) for s, _ in gaps] == [1233.7, 1038.0, 1005.9, 689.1, 296.1]
    assert [name for _, name in gaps] == ["bench.block"] * 5
