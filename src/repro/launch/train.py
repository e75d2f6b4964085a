"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch starcoder2-3b \
        --reduced --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

``--reduced`` runs the CPU-sized config (smoke/demo); without it the full
config is used (requires a real TPU slice; the multi-pod dry-run proves
the sharded program compiles for the production mesh).
"""
from __future__ import annotations

import argparse
import logging

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import ShapeSpec
from repro.launch.cache import enable_compilation_cache
from repro.train.trainer import TrainConfig, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="starcoder2-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--grad-compression", action="store_true")
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    enable_compilation_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = ShapeSpec("cli_train", "train", args.seq, args.batch)
    tcfg = TrainConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                       ckpt_dir=args.ckpt_dir,
                       grad_compression=args.grad_compression)
    trainer = Trainer(cfg, shape, tcfg)
    trainer.run()
    losses = [s["loss"] for s in trainer.stats]
    print(f"done: {len(losses)} steps, loss {losses[0]:.3f} -> {losses[-1]:.3f}, "
          f"stragglers={len(trainer.straggler_events)}")


if __name__ == "__main__":
    main()
