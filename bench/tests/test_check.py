"""The check that decides `correct`, driven through the whole harness on
the CPU at a small size: the program passes, the control and each fault a
GEMM cell can have (an answer altered where it is produced; half of the
rows left out; fewer moduli than the configuration states) fail.  Faults
a GEMM cell cannot have (state left unchanged, a missing exchange between
chips) have no test here."""
from __future__ import annotations

import dataclasses
import json
import time

import pytest

from bench import harness
from bench.control import control_entry

CELLS = [w["name"] for w in json.loads((harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]
SHAPE = {"m": 64, "n": 64, "k": 2048}
SEED = 2**31 + 12345


def _run(cell, fault=None):
    return harness.run(cell, SEED, 0.2, False, t_start=time.perf_counter(),
                       chip=False, shape=SHAPE, fault=fault)


def _altered(fn):
    """A fault hook: the program's Entry with `fn` applied to each output
    array (each plane of a planar output) before it is returned."""
    import jax

    def hook(prepare, config, ops, span):
        entry = prepare()

        def call():
            return jax.block_until_ready(jax.tree.map(fn, entry.call()))

        return dataclasses.replace(entry, call=call)

    return hook


def _alter_one(x):
    return x.at[5, 7].multiply(1.001)


def _drop_half_rows(x):
    return x.at[x.shape[0] // 2:].set(0)


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    res = _run(cell, fault=control_entry)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", [_alter_one, _drop_half_rows],
                         ids=["answer_altered", "half_rows_left_out"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault):
    res = _run(cell, fault=_altered(fault))
    assert not res["correct"], res["checks"]
    assert res["failed"] == 1


def _fewer_moduli(cell):
    """A fault hook: the cell's program built with two moduli fewer than
    the configuration states."""
    import importlib

    driver = importlib.import_module(f"bench.drivers.{harness.load_cell(cell).driver}")

    def hook(prepare, config, ops, span):
        policy = dict(config["policy"], n_moduli=config["n_moduli"] - 2)
        return driver.prepare(dict(config, policy=policy), ops, span)

    return hook


@pytest.mark.parametrize("cell", CELLS)
def test_fewer_moduli_is_not_correct(cell):
    res = _run(cell, fault=_fewer_moduli(cell))
    assert res["checks"]["moduli_gap"]["value"] == 2
    assert not res["correct"], res["checks"]
