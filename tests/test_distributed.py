"""Distribution: sharding-rule resolution (unit) + multi-device behaviours
(subprocess with xla_force_host_platform_device_count=8): compressed
gradient psum, elastic resharding, sharded train-step parity."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_reduced
from repro.distributed.sharding import (
    DEFAULT_RULES,
    optimizer_spec,
    pspec_for_axes,
    tree_pspecs,
)
from repro.models import Model
from repro.models.params import _map_like


def _mesh11():
    return jax.make_mesh((1, 1), ("data", "model"))


def test_pspec_resolution_rules():
    mesh = _mesh11()
    assert pspec_for_axes(("vocab", "embed"), DEFAULT_RULES, mesh) == P("model", None)
    # size-aware: indivisible dims drop to replicated
    assert pspec_for_axes(("experts",), DEFAULT_RULES, mesh, (40,)) == P("model")
    mesh16 = jax.make_mesh((1,), ("model",))
    # left-to-right precedence: one mesh axis used once
    spec = pspec_for_axes(("experts", "embed", "ff"), DEFAULT_RULES, mesh16)
    assert spec == P("model", None, None)


def test_optimizer_spec_zero1():
    mesh = _mesh11()
    spec = optimizer_spec(P(None, "model"), (64, 128), mesh)
    assert spec == P("data", "model")
    # indivisible first dim falls through to the next free axis (abstract
    # 2-way data mesh: only .shape is consulted)
    from jax.sharding import AbstractMesh

    amesh = AbstractMesh((2, 1), ("data", "model"))
    spec2 = optimizer_spec(P(None, None), (3, 64), amesh)
    assert spec2 == P(None, "data")


def test_tree_pspecs_cover_all_archs():
    mesh = _mesh11()
    for arch in ("qwen2.5-32b", "deepseek-moe-16b", "mamba2-130m", "recurrentgemma-2b"):
        model = Model(get_reduced(arch))
        specs = tree_pspecs(model.abstract_params(), DEFAULT_RULES, mesh)
        flat = jax.tree.leaves(
            _map_like(specs, lambda _, s: 1) if False else specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        assert len(flat) > 0


_SUBPROCESS_COMMON = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
    # pin the platform: jax's backend discovery in the stripped subprocess
    # env takes minutes without it (this box is CPU-only anyway)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    import repro
    from repro.launch.mesh import make_mesh
    """
)


def _run_sub(body: str, devices: int = 8):
    code = _SUBPROCESS_COMMON.format(devices=devices) + textwrap.dedent(body)
    res = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=420,
        env={
            "PYTHONPATH": "src",
            "PATH": "/usr/bin:/bin",
            "HOME": os.environ.get("HOME", ""),
        },
        cwd=Path(__file__).resolve().parents[1],
    )
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    return res.stdout


import pytest

# The 8-host-device subprocess tests compile full (reduced) models under
# SPMD and need several minutes of CPU each — slow-profile only (pytest.ini
# deselects `slow` by default; CI's slow job runs them).


@pytest.mark.slow
def test_compressed_psum_subprocess():
    out = _run_sub(
        """
        from functools import partial
        from repro.distributed.compression import error_feedback_psum
        mesh = jax.make_mesh((8,), ("data",))
        x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 64)), jnp.float32)
        err0 = jnp.zeros((8, 64), jnp.float32)

        @partial(jax.shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
                 out_specs=(P("data"), P("data")))
        def red(g, e):
            m, ne = error_feedback_psum(g[0], e[0], "data")
            return m[None], ne[None]

        mean, err = red(x, err0)
        true_mean = jnp.mean(x, axis=0)
        q_err = float(jnp.max(jnp.abs(mean[0] - true_mean)))
        assert q_err < 0.05, q_err                     # int8-level accuracy
        # error feedback: the residual equals what quantization dropped
        total_err = np.asarray(err).sum(0)
        # second round with zero new gradient recovers the dropped mass
        mean2, _ = red(jnp.zeros_like(x), err)
        recovered = mean[0] + mean2[0]
        q2 = float(jnp.max(jnp.abs(recovered - true_mean)))
        assert q2 < q_err + 1e-6
        print("OK", q_err)
        """
    )
    assert "OK" in out


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    out = _run_sub(
        """
        import dataclasses
        from repro.configs import get_reduced
        from repro.models import Model
        from repro.optim import AdamWConfig
        from repro.train.step import make_train_step, init_state
        cfg = dataclasses.replace(get_reduced("qwen2.5-32b"), dtype="float32", remat=False)
        model = Model(cfg)
        opt_cfg = AdamWConfig(lr=1e-3)
        rngs = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rngs.integers(0, cfg.vocab, (8, 32)), jnp.int32)}
        # single-device reference
        step1, _ = make_train_step(model, opt_cfg, donate=False)
        p1, o1 = init_state(model, opt_cfg, jax.random.PRNGKey(0))
        p1n, o1n, m1 = step1(p1, o1, batch)
        # 4x2 mesh (DPxTP)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        step2, sh = make_train_step(model, opt_cfg, mesh=mesh, donate=False)
        p2, o2 = init_state(model, opt_cfg, jax.random.PRNGKey(0), sh)
        p2n, o2n, m2 = step2(p2, o2, batch)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4, (m1["loss"], m2["loss"])
        for a, b in zip(jax.tree.leaves(p1n), jax.tree.leaves(p2n)):
            # f32 reduction-order noise across shardings gets amplified by
            # Adam's rsqrt for near-zero second moments on isolated elements:
            # demand tight agreement for 99.99% of elements and a small
            # absolute bound on the stragglers.
            # (XLA CPU reduction tiling varies with host threading, so the
            # tail is load-dependent: gate the bulk + a loose abs cap.)
            d = np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))
            scale = np.maximum(np.abs(np.asarray(a, np.float32)), 1e-3)
            rel = d / scale
            assert float(np.quantile(rel, 0.999)) < 1e-2, float(rel.max())
            assert float(d.max()) < 2e-2, float(d.max())
        print("OK", float(m1["loss"]))
        """
    )
    assert "OK" in out


@pytest.mark.slow
def test_elastic_reshard_subprocess(tmp_path):
    out = _run_sub(
        f"""
        import dataclasses
        from repro.configs import get_reduced
        from repro.models import Model
        from repro.checkpoint import Checkpointer
        from repro.distributed.elastic import elastic_restore
        cfg = dataclasses.replace(get_reduced("starcoder2-3b"), dtype="float32")
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(1))
        ck = Checkpointer(r"{tmp_path}")
        ck.save(42, params)
        # 'failure': continue on a smaller mesh (8 -> 4 devices)
        devs = jax.devices()[:4]
        import jax.sharding as jsh
        new_mesh = jsh.Mesh(np.asarray(devs).reshape(2, 2), ("data", "model"))
        step, params2 = elastic_restore(r"{tmp_path}", model.abstract_params(), new_mesh)
        assert step == 42
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # restored params live on the new mesh
        leaf = jax.tree.leaves(params2)[0]
        assert set(leaf.sharding.mesh.devices.flat) <= set(devs)
        print("OK")
        """
    )
    assert "OK" in out


def test_emulated_train_step_2device_mesh():
    """Regression (ROADMAP, found in PR 4): `launch.train --backend
    ozaki2_* --mesh 2x1` died in XLA SPMD partitioning ("compare s64[] vs
    s32[]") for every emulated execution — under jax_enable_x64 the layer
    scan's internal counter is int64, and the partitioner rejects s64
    dynamic_update_slice indices on the sharded layer stack when it
    transposes the remat scan.  `Model._run_group` now threads an explicit
    int32 carry index and gathers the stacked layer params in the body, so
    an emulated remat train step must compile and take a finite step on a
    real (forced-host) 2-device mesh.

    Not slow-marked: a deliberately tiny config keeps the subprocess under
    ~1 min — this is the only tier-1 coverage of emulated training on a
    multi-device mesh.
    """
    out = _run_sub(
        """
        from repro.core.policy import GemmPolicy
        from repro.models import Model
        from repro.models.config import ModelConfig
        from repro.train.step import make_train_step, init_state
        from repro.optim import AdamWConfig

        mesh = make_mesh((2, 1), ("data", "model"))
        cfg = ModelConfig(
            name="tiny", n_layers=2, d_model=32, vocab=64, n_heads=2,
            n_kv_heads=2, head_dim=16, d_ff=64, dtype="float32", remat=True,
            gemm_policy=GemmPolicy(
                backend="ozaki2_f32", n_moduli=4, execution="reference"
            ),
        )
        model = Model(cfg)
        step, sh = make_train_step(model, AdamWConfig(), mesh=mesh, donate=False)
        params, opt = init_state(
            model, AdamWConfig(), jax.random.PRNGKey(0), sh
        )
        batch = jax.device_put(
            {"tokens": jnp.asarray(
                np.random.default_rng(0).integers(0, cfg.vocab, (4, 16)),
                jnp.int32,
            )},
            sh["batch"],
        )
        _, _, metrics = step(params, opt, batch)
        loss = float(metrics["loss"])
        assert np.isfinite(loss), loss
        print("OK", loss)
        """,
        devices=2,
    )
    assert "OK" in out


def test_ssd_train_step_2device_mesh_and_index_widths():
    """Regression (found by `repro.analysis.ScanIndexWidthPass`, PR 7): the
    SSD block's chunk-boundary gathers used negative *integer* indexing
    (`acs[:, :, -1, :]`, `h[:, -1]`), which lowers to a dynamic_slice whose
    normalized index scalars are s64 under jax_enable_x64 — inside the remat
    layer scan, i.e. exactly the s64-index-in-scan-body shape the SPMD
    partitioner chokes on (the PR 4 bug class the two tests above pin for
    the layer scan and chunked CE).  `blocks.ssd_scan` / `rglru_prefill`
    now slice-then-squeeze (a static lax.slice).  Certify the traced train
    step index-width-clean AND take a finite emulated step on a real
    (forced-host) 2-device mesh.

    Not slow-marked: the reduced mamba2 config is tiny and this is the only
    tier-1 coverage of an SSD/recurrent block under SPMD.
    """
    out = _run_sub(
        """
        import dataclasses
        from repro.analysis import ScanIndexWidthPass
        from repro.configs import get_reduced
        from repro.core.policy import GemmPolicy
        from repro.models import Model
        from repro.train.step import make_train_step, init_state
        from repro.optim import AdamWConfig

        mesh = make_mesh((2, 1), ("data", "model"))
        cfg = dataclasses.replace(
            get_reduced("mamba2-130m"), dtype="float32", remat=True,
            gemm_policy=GemmPolicy(
                backend="ozaki2_f32", n_moduli=4, execution="reference"
            ),
        )
        model = Model(cfg)
        step, sh = make_train_step(model, AdamWConfig(), mesh=mesh, donate=False)
        params, opt = init_state(
            model, AdamWConfig(), jax.random.PRNGKey(0), sh
        )
        batch = jax.device_put(
            {"tokens": jnp.asarray(
                np.random.default_rng(0).integers(0, cfg.vocab, (4, 16)),
                jnp.int32,
            )},
            sh["batch"],
        )
        findings = ScanIndexWidthPass().run(
            jax.make_jaxpr(step)(params, opt, batch)
        )
        assert findings == [], [str(f) for f in findings]
        _, _, metrics = step(params, opt, batch)
        loss = float(metrics["loss"])
        assert np.isfinite(loss), loss
        print("OK", loss)
        """,
        devices=2,
    )
    assert "OK" in out


def test_chunked_ce_train_step_2device_mesh():
    """Regression: `loss_vocab_chunk` on a multi-device mesh died the same
    s64-vs-s32 SPMD death as the layer scan (PR 4) — `Model._chunked_ce`
    scanned *over* the vocab-slab stack as scan xs, so under jax_enable_x64
    the scan indexed the stack with an s64 counter that the partitioner
    rejects when it transposes the remat scan.  The body now gathers the
    slab with an explicit int32 carry index (xs=None), so a chunked-CE
    emulated train step must compile and take a finite step on a real
    (forced-host) 2-device mesh.  The mesh puts both devices on the
    *model* axis — the crash needs the head weights (and so the slab
    stack) actually sharded; a data-only mesh compiles even unfixed.

    Not slow-marked: the tiny config keeps the subprocess fast, and this is
    the only tier-1 coverage of the chunked-CE loss under SPMD.
    """
    out = _run_sub(
        """
        from repro.core.policy import GemmPolicy
        from repro.models import Model
        from repro.models.config import ModelConfig
        from repro.train.step import make_train_step, init_state
        from repro.optim import AdamWConfig

        mesh = make_mesh((1, 2), ("data", "model"))
        cfg = ModelConfig(
            name="tiny", n_layers=2, d_model=32, vocab=64, n_heads=2,
            n_kv_heads=2, head_dim=16, d_ff=64, dtype="float32", remat=True,
            loss_vocab_chunk=16,
            gemm_policy=GemmPolicy(
                backend="ozaki2_f32", n_moduli=4, execution="reference"
            ),
        )
        model = Model(cfg)
        step, sh = make_train_step(model, AdamWConfig(), mesh=mesh, donate=False)
        params, opt = init_state(
            model, AdamWConfig(), jax.random.PRNGKey(0), sh
        )
        batch = jax.device_put(
            {"tokens": jnp.asarray(
                np.random.default_rng(0).integers(0, cfg.vocab, (4, 16)),
                jnp.int32,
            )},
            sh["batch"],
        )
        _, _, metrics = step(params, opt, batch)
        loss = float(metrics["loss"])
        assert np.isfinite(loss), loss
        print("OK", loss)
        """,
        devices=2,
    )
    assert "OK" in out
