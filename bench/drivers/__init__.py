"""One module per entry point of the program, named `<routine>_<operands>`.

A driver module defines four functions, which the harness calls by name:

* `operands(seed, config, traffic)`: the inputs of one cell, drawn from
  the seed (host NumPy);
* `prepare(config, ops, span)` -> `Entry`: the configuration's routine
  bound to those inputs the way the traffic mix places them.  `span(name)`
  is the harness's host span (a `jax.profiler.TraceAnnotation` under a
  fixed prefix); a driver wraps each host step of a call in one;
* `errors(out, ops, seed, traffic)`: {number: value} of the output
  (`Entry.to_host` of the last call) against the plain reference;
* `work(config, traffic)`: the operations and bytes of one call
  (`bench.work.Work`).

The GEMM versions of `operands`, `errors` and `work` are below; a driver
of another kind of entry brings its own.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable


@dataclasses.dataclass
class Entry:
    """One prepared entry point: `call()` runs one product to completion."""

    #: runs one product and returns its output, ready (blocked on)
    call: Callable[[], Any]
    #: the output of `call` as a host NumPy array of the routine's dtype
    to_host: Callable[[Any], Any]
    #: the plan the program resolved, for the run's log
    plan: str
    #: the number of moduli of that plan (None: no plan, as for the control)
    n_moduli: int | None = None


def policy_of(config: dict):
    """The program's GemmPolicy: every field that `config["policy"]` names."""
    from repro.core.policy import GemmPolicy

    return GemmPolicy(**config["policy"])


def describe_plan(policy, dtype: str, m: int, k: int, n: int) -> tuple[str, int]:
    """(one line naming the plan `policy` resolves for a (m, k) x (k, n)
    product of `dtype`, the plan's number of moduli)."""
    from repro.core.policy import BACKEND_FOR_DTYPE

    plan = dataclasses.replace(policy, backend=BACKEND_FOR_DTYPE[dtype]).plan_for(m, k, n)
    blocks = len(plan.n_block_slices(n))
    line = (f"execution={policy.execution} dtype={plan.dtype} mode={plan.mode} "
            f"N={plan.n_moduli} formulation={plan.formulation} "
            f"method={plan.method} n_block={plan.n_block} blocks={blocks}")
    return line, plan.n_moduli


def gemm_operands(seed: int, config: dict, traffic: dict):
    """(A, B) of the traffic's shape, drawn from the seed with the paper's
    phi-generator at the configuration's phi and dtype."""
    from bench.operands import gemm_operands as draw

    return draw(seed, traffic["m"], traffic["n"], traffic["k"], config["phi"],
                config["dtype"])


def gemm_errors(out, ops, seed: int, traffic: dict) -> dict[str, float]:
    from bench.reference import errors

    a, b = ops
    return errors(out, a, b, seed, traffic["check_rows"])


def gemm_work(config: dict, traffic: dict):
    from bench.work import Work

    return Work(traffic["m"], traffic["n"], traffic["k"], config["n_moduli"],
                config["dtype"], config["policy"]["formulation"])
