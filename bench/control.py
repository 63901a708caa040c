"""The control: the plain product put in the program's place, computed one
precision below what the configuration states (`config["control"]`).

A run with the control in place has to come out not correct; that shows
the comparison can fail.  The benchmark's own runs never use it; the tests
in bench/tests and `bench/readings.py` do.

The precision is spelled out rather than left to XLA's matmul precision
flag, so the control computes the same on the chip and on the CPU:
`"passes": 1` is one bfloat16 pass, products of bfloat16-rounded operands
accumulated in float32 (`precision="highest"` on the rounded values).
"""
from __future__ import annotations

from bench.drivers import Entry


def _matmul(x, y, passes):
    """Real float32 x @ y at the control's number of bfloat16 passes."""
    import jax
    import jax.numpy as jnp

    if passes != 1:
        raise ValueError(f"unknown control passes {passes!r}")

    def bf16(v):
        # rounds to bfloat16 and stays float32; XLA may fold a round trip
        # through astype(bfloat16) away, but never reduce_precision
        return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)

    return jnp.matmul(bf16(x), bf16(y), precision="highest")


def control_product(a, b, passes):
    """Complex A @ B from four real products at the control's precision."""
    import jax.numpy as jnp

    ar, ai, br, bi = (jnp.real(a), jnp.imag(a), jnp.real(b), jnp.imag(b))
    cr = _matmul(ar, br, passes) - _matmul(ai, bi, passes)
    ci = _matmul(ar, bi, passes) + _matmul(ai, br, passes)
    return jnp.stack([cr, ci])


def control_entry(prepare, config, ops, span) -> Entry:
    """An Entry that computes A @ B at the control's precision on the
    device; the program (`prepare`) is not built.  Its signature is that of
    the harness's `fault` hook."""
    import functools

    import jax
    import numpy as np

    spec = config["control"]
    args = [jax.device_put(np.asarray(x, spec["dtype"])) for x in ops]
    fn = jax.jit(functools.partial(control_product, passes=spec["passes"]))
    compiled = fn.lower(*args).compile()

    def call():
        with span("dispatch"):
            out = compiled(*args)
        with span("block"):
            return jax.block_until_ready(out)

    def to_host(out):
        planes = np.asarray(out)
        return planes[0] + 1j * planes[1].astype(np.complex128)

    return Entry(call, to_host,
                 f"control: {spec['dtype']} product, passes={spec['passes']}")
