"""From a profiler trace to stage times, device busy time and idle gaps.

The device's "XLA Ops" line holds one event per operation the program ran,
named by its HLO instruction as the compiled program (`compiled.as_text()`)
writes it: name, result type, operands with their types, and for a Pallas
kernel `custom_call_target="tpu_custom_call"`.  All Pallas kernels of the
pipeline share a body name (`_kernel`) and their jit wrappers collide, so
an event is attributed by its instruction's signature:

* cast: a float operand in, int8 out;
* product: only int8 operands in (beside an int32 moduli vector), int8 out;
* garner: int8 in, float out.

Every other operation (scaling, f64 assembly, copies, XLA's f64 helpers)
counts as `outside`.  Host spans of the harness (`bench.<name>`) bound the
window and name what the host was doing in each idle gap.  The device's
clock is set against the host's by the median lag between each program's
launch on the host (`PJRT_LoadedExecutable_Execute`) and its start on the
device (the "XLA Modules" line).
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

STAGES = ("cast", "product", "garner", "kernel_other", "outside")
#: idle gaps shorter than this are rounding between adjacent ops, not gaps
MIN_GAP_S = 1e-6

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*?)\s+custom-call\(")
_TYPE = re.compile(r"\b(pred|s8|u8|s16|s32|u32|s64|bf16|f16|f32|f64)\[([\d,]*)\]")
_CONSTRAINTS = re.compile(r"operand_layout_constraints=\{(.*?)\}(?:\s*,\s*\w+=|\s*$)")


def _types(text: str) -> list[tuple[str, int]]:
    """[(dtype, element count)] of the array types written in `text`."""
    out = []
    for dtype, dims in _TYPE.findall(text):
        size = 1
        for d in filter(None, dims.split(",")):
            size *= int(d)
        out.append((dtype, size))
    return out


def stage_of(results, operands) -> str:
    """The pipeline stage of a Pallas call with these result and operand
    types, each a list of (dtype, element count)."""
    res = {d for d, _ in results}
    big = [d for d, size in operands if size > 64]  # leave out scalar tables
    ops = set(big)
    if res == {"s8"} and ops & {"f32", "f64", "bf16"}:
        return "cast"
    if res == {"s8"} and ops == {"s8"}:
        return "product"
    if res <= {"f32", "f64"} and "s8" in ops:
        return "garner"
    return "kernel_other"


def stage_of_instruction(text: str) -> str:
    """The stage of one HLO instruction, as a trace event or a line of
    compiled HLO text writes it."""
    if 'custom_call_target="tpu_custom_call"' not in text:
        return "outside"
    m = _INSTR.match(text)
    if not m:
        return "kernel_other"
    c = _CONSTRAINTS.search(text)
    operands = _types(c.group(1)) if c else []
    return stage_of(_types(m.group(2)), operands)


def short_name(text: str) -> str:
    """'%name' of an HLO instruction's text."""
    return text.split(" = ", 1)[0].strip()


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


@dataclasses.dataclass
class Trace:
    """The reduction of one traced window (times in seconds)."""

    window_s: float
    busy_s: float
    calls: int
    stage_s: dict
    op_s: dict          # {(op name, stage): seconds} in the window
    gaps: list          # [(seconds, host span)] of the idle gaps

    def per_call_ms(self, stage: str):
        """Device ms per call of `stage`, or None where the trace has none."""
        s = self.stage_s.get(stage, 0.0)
        if not self.calls or s <= 0.0:
            return None
        return 1e3 * s / self.calls

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.gaps, key=lambda g: -g[0])[:10]
        return {
            "device_ops": [[f"{name} ({stage})", s] for (name, stage), s in ops],
            "idle_gaps": [[span, s] for s, span in gaps],
        }

    def summary(self) -> str:
        per = ", ".join(f"{k} {1e3 * v / max(self.calls, 1):.4f} ms"
                        for k, v in self.stage_s.items())
        return (f"window {self.window_s:.4f}s busy {self.busy_s:.4f}s calls "
                f"{self.calls}; per call: {per}")


def reduce_events(device_ops, host_spans, prefix: str, offset_s: float = 0.0) -> Trace:
    """Reduce raw events to a `Trace`.

    device_ops: [(instruction text, start s, duration s)] of the device's
    XLA Ops line; host_spans: [(name, start s, duration s)] of host events
    named `<prefix>...`; offset_s: the device clock less the host's.  The
    trace brackets the window (it starts after the warm-up has finished and
    stops after the last call), so every device op in it is window work.
    The window runs from the first `<prefix>call` span's start to the last
    one's end.
    """
    calls = [(s, s + d) for n, s, d in host_spans if n == prefix + "call"]
    if not calls:
        raise ValueError("trace holds no host call spans")
    lo, hi = min(s for s, _ in calls), max(e for _, e in calls)
    ops = [(n, s - offset_s, s - offset_s + d) for n, s, d in device_ops]
    busy = _union([(s, e) for _, s, e in ops])
    busy_s = sum(e - s for s, e in busy)
    stage_s = {k: 0.0 for k in STAGES}
    op_s = {}
    for text, s, e in ops:
        key = (short_name(text), stage_of_instruction(text))
        stage_s[key[1]] += e - s
        op_s[key] = op_s.get(key, 0.0) + e - s
    inner = [(n, s, s + d) for n, s, d in host_spans if n != prefix + "call"]
    gaps = []
    edges = [lo] + [min(max(x, lo), hi) for iv in busy for x in iv] + [hi]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 - g0 < MIN_GAP_S:
            continue
        best, where = 0.0, "outside host spans"
        for n, s, e in inner:
            ov = min(e, g1) - max(s, g0)
            if ov > best:
                best, where = ov, n
        gaps.append((g1 - g0, where))
    return Trace(hi - lo, busy_s, len(calls), stage_s, op_s, gaps)


def clock_offset(modules, launches) -> float:
    """Device clock less host clock: the median of each program's start on
    the device less its launch on the host, paired in order.  0 where the
    two lists do not pair up."""
    if not modules or len(modules) != len(launches):
        return 0.0
    lags = sorted(m - h for m, h in zip(sorted(modules), sorted(launches)))
    return lags[len(lags) // 2]


def read_profile(data, prefix: str):
    """(device ops, host spans, clock offset s) of a `jax.profiler.ProfileData`,
    from the first TPU device plane and every host thread."""
    host_spans, launches = [], []
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(prefix):
                        host_spans.append((ev.name, ev.start_ns * 1e-9,
                                           ev.duration_ns * 1e-9))
                    elif ev.name == "PJRT_LoadedExecutable_Execute":
                        launches.append(ev.start_ns * 1e-9)
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    plane = min(devices, key=lambda p: p.name)
    device_ops, modules = [], []
    for line in plane.lines:
        if line.name == "XLA Ops":
            device_ops = [(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                          for ev in line.events]
        elif line.name == "XLA Modules":
            modules = [ev.start_ns * 1e-9 for ev in line.events]
    return device_ops, host_spans, clock_offset(modules, launches)


def reduce_profile(data, prefix: str) -> Trace:
    """Reduce a `jax.profiler.ProfileData` to a `Trace`."""
    device_ops, host_spans, offset = read_profile(data, prefix)
    return reduce_events(device_ops, host_spans, prefix, offset)


def reduce_dir(trace_dir: Path, prefix: str) -> Trace:
    """Reduce the newest `.xplane.pb` under `trace_dir`."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise ValueError(f"no profiler trace under {trace_dir}")
    return reduce_profile(ProfileData.from_file(str(files[-1])), prefix)
