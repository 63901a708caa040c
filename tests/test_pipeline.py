"""Pipeline parallelism: pipelined loss/grads == sequential (4 host devices)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest


@pytest.mark.slow  # 4-host-device SPMD subprocess: minutes of compile on CPU
def test_pipeline_matches_sequential():
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import dataclasses
        import numpy as np
        import jax, jax.numpy as jnp
        import repro
        from repro.configs import get_reduced
        from repro.models import Model
        from repro.distributed.pipeline import pipeline_loss

        cfg = dataclasses.replace(
            get_reduced("qwen2.5-32b"), n_layers=4, dtype="float32", remat=False
        )
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)), jnp.int32)}
        mesh = jax.make_mesh((4,), ("pp",))

        ref_loss, _ = model.loss(params, batch)
        pl = jax.jit(lambda p, b: pipeline_loss(model, p, b, mesh, "pp", n_micro=4))
        pipe_loss = pl(params, batch)
        assert abs(float(ref_loss) - float(pipe_loss)) < 1e-5, (
            float(ref_loss), float(pipe_loss))

        g_ref = jax.grad(lambda p: model.loss(p, batch)[0])(params)
        g_pipe = jax.grad(lambda p: pl(p, batch))(params)
        for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_pipe)):
            a = np.asarray(a, np.float32)
            b = np.asarray(b, np.float32)
            # global-scale comparison (per-element rtol is meaningless for
            # near-zero entries under f32 reduction-order noise)
            d = float(np.max(np.abs(a - b)))
            assert d <= max(1e-5, 1e-3 * float(np.max(np.abs(a)))), d
        print("PIPELINE_OK", float(pipe_loss))
        """
    )
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
    assert "PIPELINE_OK" in res.stdout
