"""Training driver.

    PYTHONPATH=src python -m repro.launch.train --arch phi4-mini-3.8b \
        --smoke --steps 100 --batch 8 --seq 64

``--smoke`` runs the reduced config on the local device; without it the
full config trains on the local device, which needs an accelerator.
"""
from __future__ import annotations

import argparse

from repro.configs import ARCHS, get_config
from repro.models.registry import build
from repro.training import (OptimizerConfig, SyntheticDataConfig,
                            train_loop)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi4-mini-3.8b", choices=ARCHS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config on the local device")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--vocab", type=int, default=0,
                    help="override vocab (smaller = faster smoke)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.vocab:
        cfg = cfg.with_(vocab_size=args.vocab)
    model = build(cfg)
    out = train_loop(
        model,
        oc=OptimizerConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                           total_steps=args.steps),
        dc=SyntheticDataConfig(batch=args.batch, seq_len=args.seq),
        num_steps=args.steps, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, resume=args.resume)
    print(f"done: loss {out['first_loss']:.4f} -> {out['final_loss']:.4f} "
          f"in {out['steps']} steps ({out['wall_s']:.1f}s)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
