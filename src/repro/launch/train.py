"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch granite-3-8b --smoke \\
      --steps 200 --coded

On this CPU container --smoke swaps in the reduced config; on a real fleet
the full config + production mesh apply (the dry-run proves those lower).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_arch, smoke_config
from repro.data import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import TPCtx, build
from repro.optim import AdamWConfig
from repro.train import Trainer, TrainerConfig, TrainConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--coded", action="store_true",
                    help="CDC-coded TP (the paper's technique)")
    ap.add_argument("--tp", type=int, default=4)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--no-resume", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    ctx = TPCtx(tp=args.tp if args.coded else 1,
                mode="coded" if args.coded else "plain")
    model = build(cfg, ctx)
    trainer = Trainer(
        model,
        TrainerConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                      ckpt_every=max(args.steps // 4, 10), log_every=5),
        AdamWConfig(lr=args.lr, total_steps=args.steps, warmup_steps=10),
        TrainConfig(microbatches=args.microbatches,
                    remat="none" if args.smoke else "full"),
        DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                   global_batch=args.batch),
    )
    out = trainer.run(resume=not args.no_resume)
    print("step,loss")
    for step, loss in out["losses"]:
        print(f"{step},{loss:.4f}")
    print(f"# wall: {out['wall_s']:.1f}s  arch={cfg.name} coded={args.coded}")


if __name__ == "__main__":
    main()
