"""CGEMM on device-resident complex64 operands: jitted `linalg.cgemm`."""
from __future__ import annotations

import functools

from . import Entry, describe_plan, policy_of
from . import gemm_errors as errors  # noqa: F401
from . import gemm_operands as operands  # noqa: F401
from . import gemm_work as work  # noqa: F401


def prepare(config, ops, span):
    import jax
    import numpy as np

    from repro import linalg

    a, b = ops
    policy = policy_of(config)
    fn = jax.jit(functools.partial(linalg.cgemm, policy=policy))
    args = [jax.device_put(np.asarray(x, np.complex64)) for x in (a, b)]
    compiled = fn.lower(*args).compile()

    def call():
        with span("dispatch"):
            out = compiled(*args)
        with span("block"):
            return jax.block_until_ready(out)

    m, k = a.shape
    plan, n_moduli = describe_plan(policy, config["dtype"], m, k, b.shape[1])
    return Entry(call, np.asarray, plan, n_moduli)
