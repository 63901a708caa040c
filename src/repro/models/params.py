"""Abstract parameter metadata -> init + sharding specs.

Every layer describes its parameters once as a pytree of `ParamMeta`
(shape, dtype, logical axis names).  From that single description we derive:
  * materialized random inits (deterministic per tree path),
  * `PartitionSpec`s via the logical-axis rules in `repro.distributed.sharding`,
  * `ShapeDtypeStruct`s for the dry-run (no allocation).
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParamMeta:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]        # logical axis names, len == ndim
    dtype: Any = jnp.bfloat16
    init: str = "normal"                # 'normal' | 'zeros' | 'ones' | custom
    scale: float | None = None          # stddev; default fan-in

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"axes {self.axes} do not match shape {self.shape}")


def _fan_in_scale(shape: tuple[int, ...]) -> float:
    fan_in = shape[0] if len(shape) > 1 else shape[-1]
    return float(1.0 / np.sqrt(max(fan_in, 1)))


def _const(shape, dtype, value) -> jnp.ndarray:
    """Constant leaf with a guaranteed-fresh device buffer.

    Eager jnp constants (zeros/ones of equal shape+dtype) share one
    executable-owned buffer, which breaks train-step donation ("donate the
    same buffer twice").  device_put of a distinct host array always
    allocates."""
    return jnp.asarray(np.full(shape, value, dtype=np.dtype(jnp.dtype(dtype))))


def _init_one(meta: ParamMeta, key: jax.Array) -> jnp.ndarray:
    if meta.init == "zeros":
        return _const(meta.shape, meta.dtype, 0)
    if meta.init == "ones":
        return _const(meta.shape, meta.dtype, 1)
    if meta.init == "future_pos":  # KV-cache position sentinel (masked slot)
        return _const(meta.shape, meta.dtype, 2**30)
    scale = meta.scale if meta.scale is not None else _fan_in_scale(meta.shape)
    return (jax.random.normal(key, meta.shape, jnp.float32) * scale).astype(meta.dtype)


def _iter_leaves(tree, path=()):
    if isinstance(tree, ParamMeta):
        yield path, tree
    elif isinstance(tree, dict):
        for k in sorted(tree):
            yield from _iter_leaves(tree[k], path + (k,))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _iter_leaves(v, path + (str(i),))
    else:
        raise TypeError(f"unexpected node {type(tree)} at {path}")


def _map_like(tree, fn, path=()):
    if isinstance(tree, ParamMeta):
        return fn(path, tree)
    if isinstance(tree, dict):
        return {k: _map_like(v, fn, path + (k,)) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(
            _map_like(v, fn, path + (str(i),)) for i, v in enumerate(tree)
        )
    raise TypeError(f"unexpected node {type(tree)} at {path}")


def materialize(abstract: Any, key: jax.Array) -> Any:
    """Deterministic init: each leaf gets fold_in(key, crc32(path)).

    A checksum, not `hash()`: Python salts string hashes per process, so
    the same seed would give different weights in every process.
    """

    def init(path, meta):
        k = key
        for part in path:
            k = jax.random.fold_in(k, zlib.crc32(part.encode()) % (2**31))
        return _init_one(meta, k)

    return _map_like(abstract, init)


def abstract_arrays(abstract: Any) -> Any:
    """ShapeDtypeStructs for .lower() (dry-run: no allocation)."""
    return _map_like(
        abstract, lambda _, m: jax.ShapeDtypeStruct(m.shape, jnp.dtype(m.dtype))
    )


def logical_axes(abstract: Any) -> Any:
    return _map_like(abstract, lambda _, m: m.axes)


def stack_metas(meta_tree: Any, n: int) -> Any:
    """Add a leading 'layers' axis to every leaf (scan-over-layers stacking)."""
    return _map_like(
        meta_tree,
        lambda _, m: ParamMeta(
            (n,) + m.shape, ("layers",) + m.axes, m.dtype, m.init, m.scale
        ),
    )


def param_bytes(abstract: Any) -> int:
    return sum(
        int(np.prod(m.shape)) * jnp.dtype(m.dtype).itemsize
        for _, m in _iter_leaves(abstract)
    )
