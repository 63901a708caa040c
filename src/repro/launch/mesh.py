"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* any jax import
(see dryrun.py) so jax.make_mesh can build the full production topology on
the CPU container.

The optional `residue` axis carves residue-plane parallelism for
`GemmPolicy(execution="sharded")` out of the model axis (total chip count is
unchanged): the N int8 residue planes of every emulated GEMM shard over it,
m/n shard over data/model as usual, and only the reconstructed output is
psum-combined (see `distributed/sharded_gemm.py`).  With `residue=1` the
mesh shapes are exactly the pre-existing 2- and 3-axis layouts.

Every mesh here is built by `make_mesh`, whose axes are all Auto: the
models place their activations with bare-`PartitionSpec`
`with_sharding_constraint` calls and let the partitioner propagate the
rest, which `jax.make_mesh`'s default Explicit axes refuse (the embedding
gather at the top of the model raises a `ShardingTypeError`).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """`jax.make_mesh` with every axis Auto (see the module docstring)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False, residue: int = 1):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod.

    residue > 1 splits the 16-way model axis into (model // residue,
    residue) and appends a 'residue' mesh axis for sharded emulated GEMMs.
    """
    model = 16
    if residue > 1:
        if model % residue:
            raise ValueError(f"residue={residue} must divide the model axis ({model})")
        shape = (2, 16, model // residue, residue) if multi_pod else (
            16, model // residue, residue
        )
        axes = (
            ("pod", "data", "model", "residue")
            if multi_pod
            else ("data", "model", "residue")
        )
        return make_mesh(shape, axes)
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, residue: int = 1):
    """Small mesh over whatever devices exist (tests/examples).

    residue > 1 appends a 'residue' axis (clamped like the others); with
    residue == 1 the mesh keeps the historical 2-axis ('data', 'model')
    layout.
    """
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(1, n // data))
    if residue > 1:
        residue = min(residue, max(1, n // (data * model)))
        return make_mesh((data, model, residue), ("data", "model", "residue"))
    return make_mesh((data, model), ("data", "model"))
