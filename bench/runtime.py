"""The runtime's host events around each device program: a clock that joins
host and device, and the idle time between programs, split.

    python3 bench/runtime.py <trace dir or .xplane.pb[.gz]>

For each launch the TPU runtime writes host events (names below) around
the device program the "XLA Modules" line of the device plane shows:

* `PJRT_LoadedExecutable_Execute`: the host asks for the launch;
* `DoEnqueueProgram`: the runtime enqueues the program on the device, so
  the program cannot start before this event starts;
* `tpu::System::Execute=>Done`: the host sees the program done, so the
  program cannot end after this event starts.

Those two bounds bracket the device clock less the host's: `lo` = max over
programs of (end - done), `hi` = min over programs of (start - enqueue).
The offset is the bracket's midpoint, and its width says how well the
events pin it.  Where the runtime events are missing, the estimate falls
back to the median lag of each program's start after its launch
(`bench.trace.clock_offset`), with no bounds.

The idle time between consecutive programs i and i + 1 splits, on the
host's clock alone, into `host` (the end of call i's `bench.block` span to
call i + 1's launch), `launch` (that launch to its enqueue) and `sync`
(the device's idle between the two programs, on its own clock, less the
other two): the device-to-host round trip.  Where the events do not pair
one to one with the programs, in order, there is no split.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import statistics
import sys
from pathlib import Path

LAUNCH = "PJRT_LoadedExecutable_Execute"
ENQUEUE = "DoEnqueueProgram"
DONE = "tpu::System::Execute=>Done"
BLOCK = "bench.block"


@dataclasses.dataclass
class Programs:
    """The device's programs and the host events around them (seconds;
    each list in time order)."""

    programs: list   # [(start, end)] on the device's clock
    launches: list   # starts of LAUNCH, host clock
    enqueues: list   # starts of ENQUEUE
    dones: list      # starts of DONE
    blocks: list     # [(start, end)] of the BLOCK spans


def read_profile(data) -> Programs:
    """`Programs` of a `jax.profiler.ProfileData`: the first TPU device
    plane's programs and every host thread's runtime events."""
    host = {LAUNCH: [], ENQUEUE: [], DONE: [], BLOCK: []}
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in host:
                        s = ev.start_ns * 1e-9
                        host[ev.name].append((s, s + ev.duration_ns * 1e-9))
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    plane = min(devices, key=lambda p: p.name)
    programs = []
    for line in plane.lines:
        if line.name == "XLA Modules":
            programs = sorted((ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                              for ev in line.events)
    starts = {k: sorted(s for s, _ in v) for k, v in host.items()}
    return Programs(programs, starts[LAUNCH], starts[ENQUEUE], starts[DONE],
                    sorted(host[BLOCK]))


def load(path) -> Programs:
    """`Programs` of a trace file (`.xplane.pb`, or gzipped), or of the
    newest `.xplane.pb` under a directory."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.is_dir():
        files = sorted(path.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
        if not files:
            raise ValueError(f"no profiler trace under {path}")
        path = files[-1]
    raw = path.read_bytes()
    if path.suffix == ".gz":
        raw = gzip.decompress(raw)
    return read_profile(ProfileData.from_serialized_xspace(raw))


def clock_bracket(p: Programs) -> tuple[float, float] | None:
    """(lo, hi): the bounds the runtime events put on the device clock less
    the host's, in seconds; None where they do not pair with the programs.
    lo > hi would mean the events contradict each other."""
    n = len(p.programs)
    if not n or len(p.enqueues) != n or len(p.dones) != n:
        return None
    lo = max(end - done for (_, end), done in zip(p.programs, p.dones))
    hi = min(start - enq for (start, _), enq in zip(p.programs, p.enqueues))
    return lo, hi


def clock_offset(p: Programs) -> tuple[float, str]:
    """(device clock less host clock in seconds, how it was found): the
    bracket's midpoint, or the median launch lag where the runtime events
    are missing."""
    bracket = clock_bracket(p)
    if bracket is not None:
        return (bracket[0] + bracket[1]) / 2, "bracket"
    from bench.trace import clock_offset as median_lag

    return (median_lag([s for s, _ in p.programs], p.launches),
            "median launch lag (runtime enqueue/done events missing)")


def idle_gaps(p: Programs) -> list[dict] | None:
    """{`host`, `launch`, `sync`, `device_idle`} seconds of each gap between
    consecutive programs; None where the events do not pair one to one,
    in order, with the programs."""
    n = len(p.programs)
    if n < 2 or any(len(v) != n for v in (p.launches, p.enqueues, p.blocks)):
        return None
    out = []
    for i in range(n - 1):
        block_end = p.blocks[i][1]
        go, enq = p.launches[i + 1], p.enqueues[i + 1]
        if not p.launches[i] <= p.blocks[i][0] <= block_end <= go <= enq:
            return None
        idle = p.programs[i + 1][0] - p.programs[i][1]
        out.append({"host": go - block_end, "launch": enq - go,
                    "sync": idle - (go - block_end) - (enq - go),
                    "device_idle": idle})
    return out


def idle_split(p: Programs) -> dict | None:
    """The mean of `idle_gaps` over the gaps, with their count `gaps`."""
    gaps = idle_gaps(p)
    if gaps is None:
        return None
    out = {k: statistics.fmean(g[k] for g in gaps) for k in gaps[0]}
    out["gaps"] = len(gaps)
    return out


def summary(p: Programs) -> dict:
    """The bracket (us), the offset and its source, and the idle split (us)."""
    bracket = clock_bracket(p)
    offset, how = clock_offset(p)
    split = idle_split(p)
    out = {"programs": len(p.programs), "clock_offset_us": 1e6 * offset,
           "clock_source": how}
    if bracket is not None:
        out["clock_bracket_us"] = [1e6 * bracket[0], 1e6 * bracket[1]]
        out["clock_bracket_width_us"] = 1e6 * (bracket[1] - bracket[0])
    if split is not None:
        out["idle_split_us"] = {k: (v if k == "gaps" else 1e6 * v)
                                for k, v in split.items()}
    return out


def main(argv=None) -> int:
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    for path in (argv if argv is not None else sys.argv[1:]):
        print(json.dumps({"trace": str(path), **summary(load(path))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
