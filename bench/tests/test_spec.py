"""BENCHMARK.json and the files it names agree: every metric's reader
states the same unit, direction, source, layer and moved metric, and every
cell's configuration, traffic mix, limits and driver exist."""
from __future__ import annotations

import importlib
import json

import pytest

from bench import harness

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader_matches_spec(metric):
    mod = harness.metric_module(metric["name"])
    assert (mod.NAME, mod.UNIT, mod.BETTER, mod.SOURCE) == (
        metric["name"], metric["unit"], metric["better"], metric["source"])
    if "layer" in metric:
        assert (mod.LAYER, mod.MOVES) == (metric["layer"], metric["moves"])


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_files_exist(cell):
    c = harness.load_cell(cell)
    driver = importlib.import_module(f"bench.drivers.{c.driver}")
    for fn in ("operands", "prepare", "errors", "work"):
        assert callable(getattr(driver, fn)), fn
    assert c.limits and all("limit" in v for v in c.limits.values())
    assert c.end_to_end and c.per_layer
