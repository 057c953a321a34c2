"""Training launcher.

Two modes:

* **host mode** (default; CPU or single accelerator): runs the reduced or
  100M-class config through the fault-tolerant training loop
  (`repro.train.loop`) — checkpointing, restart, straggler monitoring.
* **pod mode** (`--mesh pod|multipod`): builds the production mesh,
  installs the distribution context (shard_map layers pick it up), and
  runs the pjit train step with the sharding rules from
  `distributed/sharding.py`.  On this CPU container that is exercised via
  `--dry-run`, which lowers + compiles and prints the roofline terms (same
  path as `repro.launch.dryrun`); on a real pod remove `--dry-run`.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --steps 50
  PYTHONPATH=src python -m repro.launch.train --arch gemma2-9b \
      --mesh multipod --shape train_4k --dry-run
"""
import os

if __name__ == "__main__" and os.environ.get("REPRO_FORCE_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_FORCE_DEVICES"])

import argparse
import json
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
                    help="full assigned config (pod mode)")
    ap.add_argument("--mesh", choices=("host", "pod", "multipod"),
                    default="host")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--dry-run", action="store_true",
                    help="pod mode: lower+compile only, print roofline")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.mesh != "host":
        # pod path — same lowering as the multi-pod dry-run deliverable
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=512")
        from repro.launch import dryrun as D
        rep = D.lower_cell(args.arch, args.shape,
                           multi_pod=args.mesh == "multipod",
                           microbatches=args.microbatches)
        print(json.dumps({k: rep[k] for k in
                          ("arch", "shape", "mesh", "chips", "dominant",
                           "t_compute_s", "t_memory_s", "t_collective_s",
                           "roofline_fraction")}, indent=1))
        if not args.dry_run:
            print("NOTE: execution on the production mesh requires real "
                  "TPU/TRN hosts; this container compiled the step "
                  "successfully and stopped (implicit --dry-run).")
        return

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
