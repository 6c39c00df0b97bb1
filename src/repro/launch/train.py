"""Training launcher for the production mesh.

  # real run (add --reduced for the toy variant on a CPU):
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m --steps 100
  # compile-only against the full 16x16 / 2x16x16 mesh:
  PYTHONPATH=src python -m repro.launch.train --arch deepseek-67b --dry-run
"""
import argparse
import os
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--reduced", action="store_true",
                    help="train the toy variant (ModelConfig.reduced)")
    args = ap.parse_args()

    if args.dry_run:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=512").strip()
        from repro.launch.dryrun import dry_run_one
        rec = dry_run_one(args.arch, "train_4k", multi_pod=args.multi_pod)
        sys.exit(0 if rec["status"] in ("ok", "skipped") else 1)

    from repro.configs import get_config
    from repro.training.data import DataConfig
    from repro.training.optimizer import OptimizerConfig
    from repro.training.train_loop import TrainerConfig, train

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    out = train(cfg, DataConfig(batch_size=4, seq_len=256),
                OptimizerConfig(warmup_steps=20, total_steps=args.steps),
                TrainerConfig(steps=args.steps, log_every=10),
                on_metrics=lambda m: print(m))
    print(f"final loss: {out['history'][-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
