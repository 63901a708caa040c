"""Logical-axis sharding rules (MaxText-style) for the production mesh.

Parameters/caches declare logical axis names in their ParamMeta ('vocab',
'ff', 'qkv', 'experts', ...); these rules map them onto the physical mesh
axes ('pod', 'data', 'model').  Changing the parallelism layout = changing
this table, not the model code.

Alongside the parameter rules live the *residue-plane* rules of the sharded
emulated GEMM (`GemmPolicy(execution="sharded")`): the (N, m, k) / (N, k, n)
int8 residue stacks shard their plane dimension N over the 'residue' mesh
axis (falling back to 'model' when the mesh has no dedicated residue axis),
and m/n shard like a normal GEMM — m over 'data', n over 'model' unless the
residue fallback claimed it.  `resolve_gemm_axes` performs that resolution
size-aware (indivisible m/n drop to replicated, exactly like the parameter
rules), and `residue_plane_specs` spells the resulting PartitionSpecs for
every array of the pipeline.  K is never sharded: each shard contracts the
full k so the int8 planes it produces are complete, and only the exact f64
partial-reconstruction planes are ever communicated (one psum per output
block — see `distributed/sharded_gemm.py`).
"""
from __future__ import annotations

import dataclasses

from typing import Any, Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.params import ParamMeta, _map_like

RESIDUE_AXIS = "residue"

# tensor-parallel over 'model'; DP/batch over ('pod','data'); ZeRO-1 for
# optimizer state adds 'data' on the first free axis (see optimizer_spec).
# The KV cache shards its *sequence* dim over 'model' (flash-decoding style)
# because kv_heads (1-24 on the assigned archs) rarely divide the 16-way
# model axis, while the 32k/512k cache length always does.
DEFAULT_RULES: dict[str, Any] = {
    "vocab": "model",
    "ff": "model",
    "qkv": "model",
    "kv_qkv": "model",
    "heads": "model",
    "kv_heads": None,
    "kv_seq": "model",
    "experts": "model",      # expert parallelism
    "ssm_inner": "model",
    "embed": None,
    "layers": None,          # scan axis (pipeline axis when --pp is used)
    "batch": ("pod", "data"),
    "seq": None,             # flipped to 'model' under sequence parallelism
}


def _mesh_axes(mesh: Mesh) -> set[str]:
    return set(mesh.axis_names)


def _axes_size(target, mesh: Mesh) -> int:
    if isinstance(target, (tuple, list)):
        n = 1
        for t in target:
            n *= mesh.shape[t]
        return n
    return mesh.shape[target]


def _resolve(
    axis: str | None, rules: Mapping[str, Any], mesh: Mesh, dim=None, used=None
):
    """Map a logical axis onto mesh axes; drop to replicated when the mesh
    axes are absent, already claimed by an earlier dimension (left-to-right
    precedence — e.g. MoE experts take 'model' before the per-expert ff), or
    the dimension size is not divisible (pjit arguments require exact
    divisibility)."""
    if axis is None:
        return None
    target = rules.get(axis, None)
    if target is None:
        return None
    used = used if used is not None else set()
    if isinstance(target, (tuple, list)):
        kept = tuple(
            t for t in target if t in _mesh_axes(mesh) and t not in used
        )
        if not kept:
            return None
        if dim is not None and dim % _axes_size(kept, mesh):
            return None
        used.update(kept)
        return kept
    if target not in _mesh_axes(mesh) or target in used:
        return None
    if dim is not None and dim % mesh.shape[target]:
        return None
    used.add(target)
    return target


def pspec_for_axes(
    axes: Sequence[str | None], rules, mesh: Mesh, shape=None
) -> P:
    dims = shape if shape is not None else [None] * len(axes)
    used: set = set()
    return P(*[_resolve(a, rules, mesh, d, used) for a, d in zip(axes, dims)])


def pspec_for_meta(meta: ParamMeta, rules, mesh: Mesh) -> P:
    return pspec_for_axes(meta.axes, rules, mesh, meta.shape)


def tree_pspecs(abstract_params, rules, mesh: Mesh):
    """ParamMeta tree -> PartitionSpec tree (size-aware)."""
    return _map_like(abstract_params, lambda _, m: pspec_for_meta(m, rules, mesh))


def tree_shardings(abstract_params, rules, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree_pspecs(abstract_params, rules, mesh),
        is_leaf=lambda x: isinstance(x, P),
    )


def optimizer_spec(param_spec: P, shape, mesh: Mesh) -> P:
    """ZeRO-1: shard optimizer state over 'data' on the first free axis
    whose size divides the data axis.

    The m/v/master leaves mirror the parameter but additionally split one
    unsharded dimension across the data axis, so AdamW state for the
    26-32B archs fits v5e HBM (DESIGN.md S4).
    """
    if "data" not in _mesh_axes(mesh):
        return param_spec
    nd = mesh.shape["data"]
    parts = list(param_spec)
    parts += [None] * (len(shape) - len(parts))
    used = {
        a
        for p in parts
        if p is not None
        for a in (p if isinstance(p, (tuple, list)) else (p,))
    }
    if "data" in used:  # already data-sharded (e.g. ZeRO-3 param rules)
        return P(*parts)
    for i, (p, d) in enumerate(zip(parts, shape)):
        if p is None and d % nd == 0:
            parts[i] = "data"
            return P(*parts)
    return param_spec


# ------------------------------------------- sharded residue GEMM resolution


@dataclasses.dataclass(frozen=True)
class GemmShardAxes:
    """Resolved mesh axes of one sharded emulated GEMM (names or None).

    `residue` carries the N residue planes, `m` the output rows, `n` the
    output columns.  Hashable (rides inside the jit-static ShardedBackend).
    """

    residue: str | None = None
    m: str | None = None
    n: str | None = None

    def sizes(self, mesh: Mesh) -> tuple[int, int, int]:
        """(residue_shards, m_shards, n_shards) on `mesh`."""
        sz = lambda ax: mesh.shape[ax] if ax is not None else 1  # noqa: E731
        return sz(self.residue), sz(self.m), sz(self.n)


def resolve_gemm_axes(
    mesh: Mesh,
    m: int | None = None,
    n: int | None = None,
    overrides: tuple | None = None,
) -> GemmShardAxes:
    """Map the (residue, m, n) logical GEMM axes onto `mesh`.

    residue -> 'residue' when the mesh has one, else 'model'; m -> 'data';
    n -> 'model' unless the residue fallback already claimed it (one mesh
    axis is used at most once, same precedence rule as `_resolve`).  With
    shape hints, an m/n axis whose size does not divide the dimension drops
    to replicated (shard_map requires exact divisibility; the residue axis
    never drops — plane chunks zero-pad instead).  `overrides` is the
    policy's explicit (residue, m, n) name triple, taken verbatim apart
    from the divisibility check.
    """
    names = set(mesh.axis_names)
    if overrides is not None:
        residue, m_ax, n_ax = overrides
        for ax in (residue, m_ax, n_ax):
            if ax is not None and ax not in names:
                raise ValueError(
                    f"shard axis {ax!r} not on mesh axes {tuple(mesh.axis_names)}"
                )
        given = [ax for ax in (residue, m_ax, n_ax) if ax is not None]
        if len(given) != len(set(given)):
            # one mesh axis per role: e.g. residue and n both on 'model'
            # would psum partial outputs computed from DIFFERENT column
            # tiles — silently wrong, so reject it here
            raise ValueError(
                f"shard_axes must use each mesh axis at most once; got "
                f"(residue={residue!r}, m={m_ax!r}, n={n_ax!r})"
            )
    else:
        residue = (
            RESIDUE_AXIS
            if RESIDUE_AXIS in names
            else ("model" if "model" in names else None)
        )
        m_ax = "data" if "data" in names else None
        n_ax = "model" if "model" in names and residue != "model" else None
    if m_ax is not None and m is not None and m % mesh.shape[m_ax]:
        m_ax = None
    if n_ax is not None and n is not None and n % mesh.shape[n_ax]:
        n_ax = None
    return GemmShardAxes(residue=residue, m=m_ax, n=n_ax)


def residue_plane_specs(axes: GemmShardAxes) -> dict[str, P]:
    """PartitionSpecs of every array in the sharded residue pipeline.

    The spec table is the distributed design in one place: operands split
    rows/columns only, residue stacks additionally split the plane
    dimension, the exact int32 partial-reconstruction planes are the ONLY
    psum payload, and the reconstructed output is sharded like a normal
    GEMM result (no int8 array ever appears in a collective).
    """
    return {
        "a": P(axes.m, None),                       # (m, k) operand
        "b": P(None, axes.n),                       # (k, n) operand
        "a_residues": P(axes.residue, axes.m, None),  # (N, m, k) int8
        "b_residues": P(axes.residue, None, axes.n),  # (N, k, n) int8
        "product_residues": P(axes.residue, axes.m, axes.n),  # (N, m, n)
        "partial": P(None, axes.m, axes.n),         # (parts, m, n) f64, psum
        "out": P(axes.m, axes.n),                   # (m, n) reconstructed
    }


def batch_pspec(mesh: Mesh, rules=None) -> P:
    rules = rules or DEFAULT_RULES
    return P(_resolve("batch", rules, mesh))


def batch_sharding(mesh: Mesh, rules=None) -> NamedSharding:
    return NamedSharding(mesh, batch_pspec(mesh, rules))
