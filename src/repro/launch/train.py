"""End-to-end training launcher.

CPU-runnable demo (smoke configs) and the production entry (full configs
on a real TPU fleet — same code path, bigger mesh):

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke \
        --steps 200 --batch 8 --seq 128
    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke \
        --rns --steps 50          # train THROUGH the RNS digit-sliced matmul
"""

from __future__ import annotations

import argparse
import dataclasses
import logging

from repro.configs.base import get_config
from repro.core.rns_matmul import RnsDotConfig
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.optim.adamw import AdamWConfig
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--rns", action="store_true",
                    help="route MLP matmuls through the RNS datapath")
    ap.add_argument("--rns-profile", default="rns9")
    args = ap.parse_args()
    enable_compile_cache()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.rns:
        cfg = dataclasses.replace(
            cfg, rns=RnsDotConfig(profile=args.rns_profile, qx=16, qw=16),
            rns_targets="mlp")
    trainer = Trainer(
        cfg,
        AdamWConfig(lr=args.lr, warmup_steps=max(10, args.steps // 20),
                    total_steps=args.steps),
        TrainerConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                      ckpt_dir=args.ckpt_dir),
        DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                   global_batch=args.batch),
    )
    state, history = trainer.run()
    print(f"final loss: {history[-1]:.4f} (from {history[0]:.4f})")


if __name__ == "__main__":
    main()
