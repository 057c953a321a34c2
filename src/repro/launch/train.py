"""Training launcher (CPU or single accelerator): runs the reduced config,
or with ``--full`` the assigned one, through the fault-tolerant training
loop (`repro.train.loop`) — checkpointing, restart, straggler monitoring.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --steps 50
"""
import os

if __name__ == "__main__" and os.environ.get("REPRO_FORCE_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_FORCE_DEVICES"])

import argparse
import tempfile


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--full", action="store_true",
                    help="full assigned config")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro import configs
    from repro.train.loop import train

    cfg = configs.get(args.arch)
    cfg = cfg if args.full else cfg.reduced()
    ckpt = args.ckpt_dir or tempfile.mkdtemp(prefix="repro_train_")
    print(f"train {cfg.name}: {args.steps} steps -> ckpt {ckpt}")
    state, losses, rep = train(
        cfg, steps=args.steps, batch=args.batch, seq=args.seq, lr=args.lr,
        microbatches=args.microbatches,
        grad_compression=args.grad_compression,
        ckpt_dir=ckpt, ckpt_every=max(args.steps // 3, 10))
    print(f"done: steps={rep.steps_run} restarts={rep.restarts} "
          f"stragglers={rep.stragglers} loss {losses[0]:.4f} -> "
          f"{losses[-1]:.4f}")


if __name__ == "__main__":
    main()
