"""Production training launcher.

On a real TPU pod slice this runs the full sharded train step on the
production mesh; on the CPU container it runs the same code path on a local
mesh with a reduced config (--tiny), or lowers-only against the production
mesh (--dry-run, equivalent to dryrun.py for one pair).

  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --tiny \
      --steps 20 --seq-len 128 --batch 8
"""
from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--tiny", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--data-axis", type=int, default=1)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--dry-run", action="store_true")
    args = ap.parse_args()

    from repro.launch import platform
    platform.enable_compile_cache()

    if args.dry_run:
        # defer to the dry-run module (sets XLA device-count flags itself)
        import subprocess
        import sys
        return subprocess.call(
            [sys.executable, "-m", "repro.launch.dryrun",
             "--arch", args.arch, "--shape", "train_4k", "--force"])

    import jax

    from repro.configs import get_config, get_tiny_config
    from repro.distributed.sharding import ShardingRules
    from repro.launch.mesh import make_host_mesh
    from repro.training import optim
    from repro.training.loop import train

    cfg = get_tiny_config(args.arch) if args.tiny else get_config(args.arch)
    constrain = None
    if args.data_axis * args.model_axis > 1:
        mesh = make_host_mesh(args.data_axis, args.model_axis)
        constrain = ShardingRules(cfg, mesh, mode="train").constrain

    opt_cfg = optim.AdamWConfig(lr=args.lr,
                                warmup_steps=max(args.steps // 10, 1),
                                total_steps=args.steps)
    state, history = train(
        cfg, steps=args.steps, seq_len=args.seq_len,
        global_batch=args.batch, opt_cfg=opt_cfg,
        microbatches=args.microbatches, constrain=constrain,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)
    print(f"final loss: {history[-1]['loss']:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
