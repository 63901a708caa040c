"""One run of one cell: set-up, a closed-loop window, the check, one line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (`configs/<name>.json`: routine, dtype,
mode, moduli, formulation, execution) and a traffic mix
(`traffic/<name>.json`: shape, where the operands live, loop and callers).
The driver `drivers/<routine>_<operands>.py` draws the inputs, binds the
configuration's routine to them, compares the output with the plain
reference and counts the work.  Metrics are read by `metrics/<name>.py`,
limits of the check come from `limits/<cell>.json`.  Nothing here names a
cell, a routine or a shape.

The run prints its plan, device, compile-cache events and how late the
loop ran on earlier lines, the compared numbers beside their limits as the
last lines of standard error, and one JSON object as the last line of
standard output.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
#: JAX's persistent compilation cache, at a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
#: where a traced run leaves its profile (one directory per cell, replaced)
TRACE_DIR = ROOT / ".bench_traces"
#: prefix of the harness's host spans in the profiler trace
SPAN_PREFIX = "bench."


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _checked_name(kind: str, name: str) -> str:
    ok = name and (name[0].isalnum() or name[0] == "_") and all(
        ch.isalnum() or ch in "_.-" for ch in name)
    if not ok or len(name) > 64:
        raise ValueError(f"bad {kind} name {name!r}")
    return name


@dataclasses.dataclass
class Cell:
    """A workload of BENCHMARK.json with its files read."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list

    @property
    def driver(self) -> str:
        return f"{self.config['routine']}_{self.traffic['operands']}"


def load_cell(name: str, spec_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = _json(spec_path)
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise SystemExit(f"bench: no workload {name!r} in {spec_path.name}; "
                         f"known: {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = _json(ROOT / conf["file"])
    traffic = _json(BENCH / "traffic" / f"{_checked_name('traffic', w['traffic'])}.json")
    limits = _json(BENCH / "limits" / f"{_checked_name('workload', name)}.json")

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return Cell(name, int(w["chips"]), config, traffic, limits,
                mine(spec["end_to_end"]), mine(spec["per_layer"]))


def metric_module(name: str):
    """The reader `metrics/<name>.py`, loaded from its file, so that a
    metric name may hold a '.' (`cast_ms.latency`)."""
    import importlib.util

    path = BENCH / "metrics" / f"{_checked_name('metric', name)}.py"
    spec = importlib.util.spec_from_file_location(f"bench.metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Run:
    """What one run measured; the metric modules read it."""

    work: object
    peaks: dict
    setup_s: float
    calls: int
    window_s: float
    latencies_s: list
    peak_bytes: int | None
    trace: object | None = None


class CompileEvents:
    """Counts of compiles and persistent-cache reads, from jax.monitoring."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = self.compiles = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, _secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def snapshot(self) -> tuple[int, int, int]:
        return self.hits, self.misses, self.compiles


def use_cache_dir() -> None:
    """Keep the persistent compilation cache in the checkout, and cache
    every program, so that only a cell's first run in a checkout compiles."""
    import jax

    # JAX's cache with a size cap (JAX_COMPILATION_CACHE_MAX_SIZE) writes
    # nothing into a directory that does not exist yet, and reads the
    # "-atime" file of every entry before each write: an entry that a
    # process without the cap wrote has none, and then no entry is written
    CACHE_DIR.mkdir(exist_ok=True)
    for entry in CACHE_DIR.glob("*-cache"):
        atime = entry.with_name(entry.name[: -len("-cache")] + "-atime")
        if not atime.exists():
            atime.write_bytes(time.time_ns().to_bytes(8, "little"))
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chip(chips: int):
    """The first device, or a non-zero exit when JAX sees no accelerator
    or fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX platform is {devs[0].platform!r}); "
                     "this benchmark measures the chip only")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[0]


def peaks_for(device_kind: str) -> dict:
    table = _json(BENCH / "peaks.json")
    if device_kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {device_kind!r} in "
                         "bench/peaks.json")
    return table[device_kind]


def span_factory(enabled: bool):
    """`span(name)`: a profiler host span named `bench.<name>`, or nothing."""
    if not enabled:
        return lambda name: contextlib.nullcontext()
    import jax

    return lambda name: jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def window(entry, seconds: float, span):
    """Closed loop of one caller: each call's result is blocked on before
    the next is sent.  Returns (calls, window s, latencies, last output,
    host gap s between calls)."""
    latencies, gap_s = [], 0.0
    out = None
    t0 = time.perf_counter()
    end = t0
    while end - t0 < seconds:
        start = time.perf_counter()
        gap_s += start - end
        out = None  # free the previous output before the next call
        with span("call"):
            out = entry.call()
        end = time.perf_counter()
        latencies.append(end - start)
    return len(latencies), end - t0, latencies, out, gap_s


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, chip: bool = True, shape: dict | None = None,
        fault=None) -> dict:
    """One run of `workload`; returns the result object.

    `chip=False`, `shape` (traffic overrides, such as m, n, k) and `fault`
    exist for the tests of the harness, which run on the CPU at small
    sizes, and for the readings of the limits (bench/readings.py).
    `fault(prepare, config, ops, span)` returns the Entry the window drives
    in place of the driver's (`prepare()`): the control, or the program
    with a fault planted."""
    cell = load_cell(workload)
    traffic = dict(cell.traffic, **(shape or {}))
    if traffic["loop"] != "closed" or traffic["callers"] != 1:
        raise SystemExit("bench: only a closed loop of one caller is built")
    use_cache_dir()
    import jax

    if chip:
        dev = require_chip(cell.chips)
    else:
        dev = jax.devices()[0]
    peaks = peaks_for(dev.device_kind) if chip else peaks_for("TPU v5 lite")
    events = CompileEvents()
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401  (the system under test)
    except ImportError as e:
        raise SystemExit(f"bench: the program is not in this checkout: {e}")

    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {CACHE_DIR}")
    config = cell.config
    driver = importlib.import_module(f"bench.drivers.{_checked_name('driver', cell.driver)}")
    t_ops = time.perf_counter()
    ops = driver.operands(seed, config, traffic)
    t_prep = time.perf_counter()
    span = span_factory(trace)

    def prepare():
        return driver.prepare(config, ops, span)

    entry = prepare() if fault is None else fault(prepare, config, ops, span)
    log(f"plan {entry.plan} traffic={traffic} driver={cell.driver}")
    t_warm = time.perf_counter()
    # warm-up: the first call runs what compiled above, the second is steady
    for _ in range(2):
        entry.call()
    setup_s = time.perf_counter() - t_start
    hits, misses, compiles = events.snapshot()
    log(f"set-up {setup_s:.3f}s: start and imports {t_ops - t_start:.3f}s, "
        f"operands {t_prep - t_ops:.3f}s, prepare (copies, lowering, compile "
        f"or cache read) {t_warm - t_prep:.3f}s, two warm-up calls "
        f"{setup_s - (t_warm - t_start):.3f}s; compile cache {hits} hits, "
        f"{misses} misses, {compiles} compiles")

    trace_dir = TRACE_DIR / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans only, no Python calls
        options.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        calls, window_s, latencies, out, gap_s = window(entry, seconds, span)
    finally:
        if trace:
            jax.profiler.stop_trace()
    mark = events.snapshot()
    log(f"window {window_s:.3f}s, {calls} calls; host gap between calls "
        f"{gap_s * 1e3 / max(calls - 1, 1):.3f} ms mean; compiles in window "
        f"{mark[2] - compiles}, cache reads {mark[0] + mark[1] - hits - misses}")
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")

    record = Run(driver.work(config, traffic), peaks, setup_s, calls, window_s,
                 latencies, peak)
    result = {"correct": False, "attempted": calls, "failed": 0}
    if trace:
        from bench import trace as tracemod

        record.trace = tracemod.reduce_dir(trace_dir, SPAN_PREFIX)
        log(f"trace {record.trace.summary()}")

    c = entry.to_host(out)
    del out
    numbers = driver.errors(c, ops, seed, traffic)
    checks = {key: {"value": numbers[key], "limit": cell.limits[key]["limit"]}
              for key in sorted(cell.limits)}
    if entry.n_moduli is not None:
        # exact: the plan the program resolved runs the configuration's N
        checks["moduli_gap"] = {"value": abs(entry.n_moduli - config["n_moduli"]),
                                "limit": 0}
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    result["correct"] = correct
    result["failed"] = 0 if correct else 1

    metrics = {}
    for spec in (cell.per_layer if trace else cell.end_to_end):
        mod = metric_module(spec["name"])
        if mod.UNIT != spec["unit"]:
            raise SystemExit(f"bench: unit of {spec['name']} is {mod.UNIT!r} in "
                             f"its module, {spec['unit']!r} in BENCHMARK.json")
        value = mod.read(record)
        if value is not None:
            metrics[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    result["metrics"] = metrics
    result["device"] = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()), "memory_peak_bytes": peak,
    }
    if trace:
        result["device"]["busy_s"] = record.trace.busy_s
        result["device"]["window_s"] = record.trace.window_s
        result["breakdown"] = record.trace.breakdown()
    for key, v in checks.items():
        log(f"check {key} {v['value']!r} <= limit {v['limit']!r}: "
            f"{'ok' if v['value'] <= v['limit'] else 'FAILED'}")
    result["checks"] = checks
    return result
