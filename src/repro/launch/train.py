"""Training launcher: --arch <id> [--smoke] with checkpointing/restart.

Runs the config at its published widths on the process's default device
(the TPU on a TPU host), with the config's own activation checkpointing
unless ``--remat`` overrides it.  The llama3-1b step that ``chip_smoke.py``
runs on one v5e::

    PYTHONPATH=src python -m repro.launch.train --arch llama3-1b \
        --seq 2048 --batch 1 --remat full --steps 8
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor"])
    ap.add_argument("--remat", choices=["none", "full"], default=None,
                    help="activation checkpointing (default: the config's)")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    from repro.configs.base import RunConfig, ShapeConfig
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import get_config, get_smoke_config
    from repro.train import train

    enable_compile_cache()
    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    if args.remat:
        cfg = cfg.scaled(remat=args.remat)
    run = RunConfig(
        model=cfg, shape=ShapeConfig("cli", args.seq, args.batch, "train"),
        learning_rate=args.lr, optimizer=args.optimizer,
        microbatch=args.microbatch,
        gradient_compression=args.grad_compression)
    res = train(run, num_steps=args.steps, checkpoint_dir=args.ckpt,
                checkpoint_every=args.ckpt_every, resume=args.resume)
    print(f"finished {res.steps} steps; final loss {res.final_loss:.4f}")


if __name__ == "__main__":
    main()
