"""repro.analysis — static certification of the residue-emulation stack.

A pass framework over traced jaxprs plus source-level lints, wired into CI
(`python -m repro.analysis --matrix smoke`).  Four jaxpr passes certify the
invariants every engine must uphold (see docs/static_analysis.md):

* :class:`OverflowPass` — int8 residue dots within ``K_CHUNK_LIMIT``, fp8
  digit dots within ``FP8_K_CHUNK_LIMIT``, provable f64 dots within the
  exact 2^53 window (paper SIII-A accumulation bound);
* :class:`CollectiveSafetyPass` — only >=32-bit (exact) arrays cross the
  mesh in collectives;
* :class:`LaunchCountPass` — `pallas_call` count equals the perfmodel's
  `kernel_launch_count` prediction;
* :class:`ScanIndexWidthPass` — no s64 index feeds indexing primitives
  inside scan bodies (the SPMD partitioner-crash bug class of PRs 5/6);
* :class:`AccuracyPass` — a plan declaring an accuracy contract
  (``EmulationPlan.rtol``, stamped by adaptive ``GemmPolicy(rtol=...)`` /
  ``mode="auto"`` policies) must have static `core.accuracy.rel_bound`
  <= the declared tolerance at the row's contraction length.

Every residue backend exposes ``analyze(plan, shape=None)`` returning the
pass suite for its engine; `passes_for_backend` is the shared resolver.

Example::

    import jax, jax.numpy as jnp
    from repro.analysis import CollectiveSafetyPass

    jaxpr = jax.make_jaxpr(jnp.matmul)(
        jnp.zeros((8, 4)), jnp.zeros((4, 2)))
    assert CollectiveSafetyPass().run(jaxpr) == []   # nothing crosses a mesh
"""
from .jaxprs import (  # noqa: F401
    EqnContext,
    count_pallas_calls,
    count_pallas_launches,
    count_primitive,
    iter_eqns,
    iter_subjaxprs,
)
from .lint import (  # noqa: F401
    EXECUTION_CLIS,
    execution_choices,
    lint_policy_surface,
    lint_repo,
)
from .passes import (  # noqa: F401
    COLLECTIVE_PRIMS,
    AccuracyPass,
    CollectiveSafetyPass,
    Finding,
    LaunchCountPass,
    OverflowPass,
    ScanIndexWidthPass,
    certify_launch_count,
    certify_partial_split,
    collect_collectives,
    expected_launch_count,
    passes_for_backend,
    run_passes,
)

__all__ = [
    "AccuracyPass",
    "EqnContext",
    "Finding",
    "OverflowPass",
    "CollectiveSafetyPass",
    "LaunchCountPass",
    "ScanIndexWidthPass",
    "COLLECTIVE_PRIMS",
    "EXECUTION_CLIS",
    "collect_collectives",
    "certify_launch_count",
    "certify_partial_split",
    "count_pallas_calls",
    "count_pallas_launches",
    "count_primitive",
    "execution_choices",
    "expected_launch_count",
    "iter_eqns",
    "iter_subjaxprs",
    "lint_policy_surface",
    "lint_repo",
    "passes_for_backend",
    "run_passes",
]
