"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m \
        --steps 100 --batch 8 --seq 256 [--mesh dxm] [--ckpt-dir DIR] \
        [--backend ozaki2_f32] [--execution kernel] [--mode accu] \
        [--formulation auto] [--n-block auto] [--rtol 1e-6] \
        [--seq-shard] [--vocab-chunk N] [--compress-dp]

The emulation flags mirror the `GemmPolicy` axes: `--backend` picks the
compute dtype class, `--execution` the residue backend (jnp reference,
modulus-batched Pallas kernels, or the per-modulus parity path), `--mode` /
`--formulation` / `--n-block` the paper's accuracy and Fig. 1 strategy knobs
('auto' consults the SIII-C perfmodel per shape).

Without --mesh the run uses one device; on a pod pass --mesh 16x16 (the
dry-run proves those configs compile for every arch).  Compiled programs
persist in the compile cache (`repro.launch.compile_cache`).
"""
from __future__ import annotations

import argparse
import dataclasses

import jax

import repro  # noqa: F401
from repro.configs import ARCHS, get_config, get_reduced
from repro.core.policy import GemmPolicy
from repro.data import DataConfig
from repro.models import Model
from repro.optim import AdamWConfig
from repro.train import TrainLoopConfig, train_loop
from repro.tune.cli import add_calibration_args, apply_calibration_args

from .compile_cache import enable_compile_cache
from .mesh import make_host_mesh, make_mesh


def parse_n_block(s: str):
    """CLI n_block: an integer or the literal 'auto' (perfmodel-driven)."""
    return "auto" if s == "auto" else int(s)


def main(argv=None) -> list[float]:
    """Parse `argv` (default: the command line), train, and return the
    per-step losses."""
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, required=True)
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="reduced config (full configs need a pod)")
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--mesh", default=None, help="DxM, e.g. 16x16")
    ap.add_argument("--ckpt-dir", default=None)
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
                    help="residue mesh-axis size (sharded execution); "
                         "appended to the --mesh layout")
    ap.add_argument("--mode", default="fast", choices=["fast", "accu", "auto"],
                    help="paper scaling mode (accuracy band); 'auto' picks "
                         "the cheapest mode meeting --rtol per shape")
    ap.add_argument("--rtol", type=float, default=None,
                    help="componentwise accuracy target: the policy "
                         "resolves the fewest moduli whose core.accuracy "
                         "bound provably meets it (required for "
                         "--mode auto)")
    ap.add_argument("--formulation", default="karatsuba",
                    choices=["karatsuba", "block_a", "block_b", "auto"],
                    help="complex Fig. 1 strategy (complex backends only)")
    ap.add_argument("--n-block", default=None, type=parse_n_block,
                    help="output-column blocking: an int or 'auto'")
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--vocab-chunk", type=int, default=None)
    add_calibration_args(ap)
    args = ap.parse_args(argv)
    apply_calibration_args(args)
    if args.mode == "auto" and args.rtol is None:
        ap.error("--mode auto needs an accuracy target: pass --rtol")

    mesh = None
    if args.mesh:
        d, m = map(int, args.mesh.split("x"))
        if args.residue > 1:
            mesh = make_mesh((d, m, args.residue), ("data", "model", "residue"))
        else:
            mesh = make_mesh((d, m), ("data", "model"))
    elif args.execution == "sharded":
        # sharded execution needs a mesh even on a single host: default to
        # every local device on the residue axis
        mesh = make_host_mesh(
            1, 1,
            residue=args.residue if args.residue > 1 else len(jax.devices()),
        )

    cfg = (get_reduced if args.reduced else get_config)(args.arch)
    over = {}
    if args.backend != "native":
        over["gemm_policy"] = GemmPolicy(
            backend=args.backend,
            mode=args.mode,
            formulation=args.formulation,
            n_block=args.n_block,
            execution=args.execution,
            mesh=mesh if args.execution == "sharded" else None,
            rtol=args.rtol,
        )
        over["dtype"] = "float32"
    if args.seq_shard:
        over["act_pspec"] = (("data",), "model", None)
    if args.vocab_chunk:
        over["loss_vocab_chunk"] = args.vocab_chunk
    if over:
        cfg = dataclasses.replace(cfg, **over)

    model = Model(cfg)
    data = DataConfig(vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch)
    loop = TrainLoopConfig(
        steps=args.steps,
        warmup=max(5, args.steps // 20),
        log_every=max(1, args.steps // 20),
        ckpt_every=max(10, args.steps // 4),
        ckpt_dir=args.ckpt_dir,
        grad_accum=args.grad_accum,
    )
    _, hist = train_loop(model, data, loop, AdamWConfig(lr=args.lr, grad_clip=5.0),
                         mesh=mesh)
    print(f"[{args.arch}] loss {hist[0]:.4f} -> {hist[-1]:.4f}")
    return hist


if __name__ == "__main__":
    main()
