"""End-to-end training launcher.

CPU-scale real runs (smoke/full archs with reduced shapes) and the
production configuration path are the same code: pick --arch, --shape (or
--steps/--batch/--seq overrides), --mode dfabric|gspmd.

Examples::

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
        --steps 50 --batch 8 --seq 64
"""
from __future__ import annotations

import argparse
import json
import os

import jax

from repro.configs.base import SHAPES, ShapeConfig, get_arch, get_smoke_arch
from repro.launch.compile_cache import use_compile_cache
from repro.models.registry import build_model
from repro.models.transformer import ModelSettings
from repro.runtime.train_loop import Trainer, TrainerConfig
from repro.utils.jax_compat import make_mesh


def step_range(text: str):
    """``A:B`` -> (A, B), steps A to B-1."""
    lo, sep, hi = text.partition(":")
    if not (sep and lo.isdigit() and hi.isdigit() and int(lo) < int(hi)):
        raise argparse.ArgumentTypeError(f"{text!r} is not A:B with A < B")
    return int(lo), int(hi)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-friendly)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mode", default="dfabric", choices=["dfabric", "gspmd"])
    ap.add_argument("--codec", default=None)
    ap.add_argument("--no-pipeline", action="store_true",
                    help="disable the overlapped slow-leg chunk pipeline "
                         "(sequential schedules, for A/B runs)")
    ap.add_argument("--no-zero1", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--mesh", default=None,
                    help="comma shape, e.g. 2,2,2 for (pod,data,model); "
                         "requires forced host devices")
    ap.add_argument("--metrics-out", default=None)
    ap.add_argument("--metrics-path", default=None,
                    help="streamed JSONL metrics (repro.obs.metrics): one "
                         "record per step as it happens, unlike the "
                         "post-hoc --metrics-out dump")
    ap.add_argument("--profile-dir", default=None,
                    help="write a jax.profiler trace (TensorBoard / Perfetto) "
                         "of --profile-steps here")
    ap.add_argument("--profile-steps", default=None, metavar="A:B",
                    type=step_range,
                    help="trace steps A to B-1 (default: every step)")
    args = ap.parse_args()
    use_compile_cache()

    arch = get_smoke_arch(args.arch) if args.smoke else get_arch(args.arch)
    if args.shape:
        shape = SHAPES[args.shape]
    else:
        shape = ShapeConfig("custom", args.seq, args.batch, "train")

    ndev = len(jax.devices())
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split(","))
        if len(dims) == 4:  # 3-tier fabric: (pod, host, data, model)
            axes = ("pod", "host", "data", "model")
        elif len(dims) < 3:
            axes = ("pod", "data", "model")[-len(dims):]
        else:
            axes = ("pod", "data", "model")
        mesh = make_mesh(dims, axes)
    else:
        mesh = make_mesh((1, ndev, 1), ("pod", "data", "model"))

    st = ModelSettings(param_dtype="float32", compute_dtype="float32",
                       remat="none", loss_chunk=min(128, shape.seq_len),
                       max_seq=shape.seq_len)
    model = build_model(arch, st)
    cfg = TrainerConfig(steps=args.steps, lr=args.lr, warmup=max(args.steps // 10, 1),
                        mode=args.mode, zero1=not args.no_zero1,
                        codec=args.codec, pipeline=not args.no_pipeline,
                        microbatches=args.microbatches,
                        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                        metrics_path=args.metrics_path,
                        profile_dir=args.profile_dir,
                        profile_steps=args.profile_steps)
    trainer = Trainer(model, mesh, shape, cfg)
    trainer.install_preemption_handler()
    out = trainer.train()
    print(f"finished at step {out['step']}; "
          f"final loss {out['metrics'][-1]['loss']:.4f}; "
          f"straggler events: {len(out['straggler_events'])}")
    if args.metrics_out:
        os.makedirs(os.path.dirname(args.metrics_out) or ".", exist_ok=True)
        with open(args.metrics_out, "w") as f:
            json.dump(out["metrics"], f, indent=1)


if __name__ == "__main__":
    main()
