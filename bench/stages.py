"""Device time per stage of the emulation, by the names the program gives.

The program runs each stage under a named scope `ozaki2.<stage>`
(`repro.core.executor.STAGES`), and the compiler keeps the scope in the
`op_name` metadata of each instruction it makes from that stage's code.
The profiler's device events carry only the instruction's text, no
metadata, so the map from an instruction's name to its stage comes from
the compiled program's text: here, from the live executable whose module
holds every op the trace saw (the program the window ran is still alive
when the metrics are read).

Where stages nest, the innermost names the op.  An op with no scope is
`unstaged`: an op the compiler makes with no source in a stage.  A program
that names no stage at all (one older than the scopes) yields no map, and
the metrics that read it report nothing.
"""
from __future__ import annotations

import re

UNSTAGED = "unstaged"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"ozaki2\.(\w+)")


def stage_map(hlo_text: str) -> dict[str, str]:
    """{'%name': stage} of every instruction of a compiled program's text
    (`compiled.as_text()`): the innermost `ozaki2.<stage>` of its
    `op_name`, else `unstaged`."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        scopes = _SCOPE.findall(op.group(1)) if op else []
        out[m.group(1)] = scopes[-1] if scopes else UNSTAGED
    return out


def live_program_text(op_names) -> str | None:
    """The compiled text of the live program whose instructions include
    every name in `op_names`, or None where no live program does."""
    import jax

    try:
        executables = jax.devices()[0].client.live_executables()
    except AttributeError:  # a runtime without the listing
        return None
    wanted = set(op_names)
    for exe in executables:
        for module in exe.hlo_modules():
            text = module.to_string()
            if wanted <= set(stage_map(text)):
                return text
    return None


def stage_seconds(op_s: dict, names: dict[str, str]) -> dict[str, float] | None:
    """{stage: device seconds} of a trace's ops ({(op name, signature
    stage): seconds}, `bench.trace.Trace.op_s`) under the map `names`;
    None where the map names no stage or misses an op."""
    if not any(s != UNSTAGED for s in names.values()):
        return None
    out: dict[str, float] = {}
    for (name, _), s in op_s.items():
        if name not in names:
            return None
        out[names[name]] = out.get(names[name], 0.0) + s
    return out


def unstaged_ops(op_s: dict, names: dict[str, str]) -> dict[str, float]:
    """{op name: device seconds} of the trace's ops that carry no stage."""
    out: dict[str, float] = {}
    for (name, _), s in op_s.items():
        if names.get(name) == UNSTAGED:
            out[name] = out.get(name, 0.0) + s
    return out


def run_stage_seconds(run) -> dict[str, float] | None:
    """`stage_seconds` of a harness run's trace, its map from the live
    program; None without a trace or a named program."""
    t = run.trace
    if t is None or not t.op_s:
        return None
    text = live_program_text(name for name, _ in t.op_s)
    return None if text is None else stage_seconds(t.op_s, stage_map(text))


def per_call_ms(run, stage: str) -> float | None:
    """Device ms per call of `stage` in a harness run, by the program's
    names; None where the program names no stage."""
    seconds = run_stage_seconds(run)
    if seconds is None or not run.trace.calls:
        return None
    return 1e3 * seconds.get(stage, 0.0) / run.trace.calls
