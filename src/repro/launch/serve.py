"""Production serving launcher (batched prefill + decode).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-32b \
        --batch 8 --prompt-len 64 --new-tokens 64 [--temperature 0.8] \
        [--backend ozaki2_f32] [--execution kernel] \
        [--prepare] [--prepared-dir DIR] [--full]

--full serves the published configuration instead of the reduced one.

An emulated --backend scopes the whole model onto that GemmPolicy via
`repro.use_policy` around config lookup (the context-scoped drop-in path);
--execution picks the residue backend (jnp reference or the batched Pallas
kernels).  --prepare residue-casts the weights once at startup with the
selected execution backend; --prepared-dir persists those planes so a
restarted server restores them instead of re-preparing.
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

import contextlib

import repro
from repro.configs import ARCHS, get_config, get_reduced
from repro.core import GemmPolicy
from repro.models import Model
from repro.serve import ServeEngine
from repro.tune.cli import add_calibration_args, apply_calibration_args

from .compile_cache import enable_compile_cache


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, required=True)
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="reduced config (the default)")
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="the published configuration")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument(
        "--prepare", action="store_true",
        help="residue-cast weights once at startup (emulated backends: "
             "amortizes the scheme's step 1 across all requests)",
    )
    ap.add_argument("--prepared-dir", default=None,
                    help="persist/restore prepared residue planes here")
    ap.add_argument("--backend", default="native",
                    choices=["native", "ozaki2_f32", "ozaki2_f64",
                             "ozaki2_c64", "ozaki2_c128"])
    ap.add_argument("--execution", default="reference",
                    choices=["reference", "kernel", "per_modulus_kernel",
                             "sharded", "fp8", "fused"],
                    help="residue backend running the emulation plan "
                         "(fp8: the e4m3 digit-GEMM engine; fused: the "
                         "one-launch megakernel)")
    ap.add_argument("--residue", type=int, default=1,
                    help="residue mesh-axis size (sharded execution)")
    ap.add_argument("--mode", default="fast",
                    choices=["fast", "accu", "auto"],
                    help="paper scaling mode; 'auto' picks the cheapest "
                         "mode meeting --rtol per shape")
    ap.add_argument("--rtol", type=float, default=None,
                    help="componentwise accuracy target (adaptive policy: "
                         "fewest moduli provably meeting it; required for "
                         "--mode auto)")
    add_calibration_args(ap)
    args = ap.parse_args(argv)
    apply_calibration_args(args)
    if args.mode == "auto" and args.rtol is None:
        ap.error("--mode auto needs an accuracy target: pass --rtol")

    scope = contextlib.nullcontext()
    if args.backend != "native":
        mesh = None
        if args.execution == "sharded":
            from repro.launch.mesh import make_host_mesh

            mesh = make_host_mesh(
                1, 1,
                residue=args.residue if args.residue > 1 else len(jax.devices()),
            )
        scope = repro.use_policy(
            GemmPolicy(backend=args.backend, execution=args.execution,
                       mesh=mesh, mode=args.mode, rtol=args.rtol)
        )
    with scope:
        cfg = (get_reduced if args.reduced else get_config)(args.arch, **(
            {} if args.backend == "native" else {"dtype": "float32"}
        ))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    npre = cfg.n_prefix_embeds if cfg.frontend else 0
    eng = ServeEngine(
        model, params,
        cache_len=args.prompt_len + npre + args.new_tokens,
        batch_size=args.batch,
        prepare=args.prepare,
        prepared_dir=args.prepared_dir,
    )
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)}
    if cfg.frontend:
        batch["prefix_embeds"] = jnp.asarray(
            rng.standard_normal((args.batch, npre, cfg.d_model)) * 0.02, jnp.float32)
    t0 = time.perf_counter()
    toks = eng.generate(batch, args.new_tokens, args.temperature,
                        jax.random.PRNGKey(1))
    dt = time.perf_counter() - t0
    print(f"[{args.arch}] {toks.shape} in {dt:.2f}s "
          f"({args.batch * args.new_tokens / dt:.1f} tok/s incl. compile)")


if __name__ == "__main__":
    main()
