"""Training launcher.

Local (reduced) run on this host:
    PYTHONPATH=src python -m repro.launch.train --arch yi-9b --steps 50 \
        --reduced --batch 8 --seq 64

Production posture: the same RunConfig drives the dry-run
(``repro.launch.dryrun``) against the 16x16 / 2x16x16 meshes; on a real
cluster this entry point would initialize jax.distributed and feed
per-host shards — the step function and shardings are identical.
"""
from __future__ import annotations

import argparse

from repro.configs.base import (AccelConfig, RunConfig, SHAPES_BY_NAME,
                                get_arch, list_archs)
from repro.launch.compile_cache import enable_compile_cache
from repro.train.trainer import train


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--remat", default="nothing")
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    run = RunConfig(arch=cfg, shape=SHAPES_BY_NAME[args.shape],
                    accel=AccelConfig(), remat=args.remat,
                    learning_rate=args.lr)
    train(run, num_steps=args.steps, checkpoint_dir=args.ckpt,
          batch_override=args.batch, seq_override=args.seq)


if __name__ == "__main__":
    main()
