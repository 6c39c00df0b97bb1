"""Training driver: a ~100M-param model for a few hundred steps on CPU.

  PYTHONPATH=src python examples/train_smoke.py [--arch mamba2-130m] [--steps 200]

(mamba2-130m is the only assigned arch that is laptop-sized at FULL config;
other archs run via their reduced variants with --reduced.)
"""
import argparse

from repro.configs import get_config
from repro.training.data import DataConfig
from repro.training.optimizer import OptimizerConfig
from repro.training.train_loop import TrainerConfig, train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-130m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt", default="")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    out = train(
        cfg,
        DataConfig(batch_size=args.batch, seq_len=args.seq),
        OptimizerConfig(lr=3e-4, warmup_steps=20, total_steps=args.steps),
        TrainerConfig(steps=args.steps, log_every=10,
                      ckpt_every=args.steps if args.ckpt else 0,
                      ckpt_dir=args.ckpt or "/tmp/repro_ckpt"),
        on_metrics=lambda m: print(
            f"step {m['step']:4d}  loss {m['loss']:7.4f}  "
            f"lr {m['lr']:.2e}  {m['tok_per_s']:.0f} tok/s"),
    )
    first, last = out["history"][0]["loss"], out["history"][-1]["loss"]
    print(f"\nloss {first:.3f} -> {last:.3f} over {args.steps} steps")


if __name__ == "__main__":
    main()
