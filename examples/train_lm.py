"""End-to-end training driver: data pipeline -> train loop -> checkpoint
-> restart, with the fault-tolerance supervisor.

Defaults are CPU-friendly (a reduced config, 60 steps); ``--full`` trains
the assigned config at its published widths.

  PYTHONPATH=src python examples/train_lm.py
  PYTHONPATH=src python examples/train_lm.py --arch mamba2-780m --steps 40
  PYTHONPATH=src python examples/train_lm.py --model-100m --steps 300  # ~100M params
"""
from __future__ import annotations

import argparse
import tempfile
from dataclasses import replace

from repro import configs
from repro.train.loop import train


def build_cfg(args) -> configs.ArchConfig:
    cfg = configs.get(args.arch)
    if args.full:
        return cfg
    if args.model_100m:
        # ~100M-param member of the same family (paper-scale example (b))
        pat = len(cfg.block_pattern)
        reps = max(1, 12 // pat)
        return replace(cfg.reduced(), name=cfg.name + "-100m",
                       d_model=768, num_layers=pat * reps, num_heads=12,
                       num_kv_heads=4, d_ff=2048, vocab_size=32_000,
                       head_dim=64)
    return cfg.reduced()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b",
                    choices=sorted(configs.ALL_ARCHS))
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", action="store_true",
                    help="int8 + error-feedback gradient compression")
    ap.add_argument("--model-100m", action="store_true",
                    help="~100M-param family member instead of reduced")
    ap.add_argument("--full", action="store_true",
                    help="full assigned config (needs a real pod)")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    cfg = build_cfg(args)
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="repro_ckpt_")
    print(f"training {cfg.name}: {args.steps} steps, batch {args.batch}, "
          f"seq {args.seq}, ckpt -> {ckpt_dir}")

    state, losses, report = train(
        cfg, steps=args.steps, batch=args.batch, seq=args.seq, lr=args.lr,
        microbatches=args.microbatches, grad_compression=args.grad_compression,
        ckpt_dir=ckpt_dir, ckpt_every=max(args.steps // 3, 10))

    print(f"\nsteps run      : {report.steps_run}")
    print(f"first loss     : {losses[0]:.4f}")
    print(f"last loss      : {losses[-1]:.4f}")
    assert losses[-1] < losses[0], "loss should decrease on synthetic data"
    print("loss decreased — training works end to end.")

    # --- restart-from-checkpoint (fault-tolerance path): num_steps is the
    # target global step, so ask for a few more than already completed
    extra = max(args.steps // 6, 5)
    print(f"\nsimulating restart from the latest checkpoint "
          f"(+{extra} steps) ...")
    state2, losses2, rep2 = train(
        cfg, steps=args.steps + extra, batch=args.batch, seq=args.seq,
        lr=args.lr, ckpt_dir=ckpt_dir, ckpt_every=10_000)
    print(f"resumed at step {rep2.resumed_from} and ran {rep2.steps_run} "
          f"more steps (loss {losses2[-1]:.4f}); checkpoint/restart works.")


if __name__ == "__main__":
    main()
