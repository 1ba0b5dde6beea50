"""End-to-end training driver example: a ~100M-param llama-family model for
a few hundred steps with checkpoint/restart and deterministic data.

This wraps launch/train.py's machinery at a width that fits this CPU
container while exercising the full substrate (sharded state, microbatched
step, async checkpoints, straggler accounting).

  PYTHONPATH=src python examples/train_lm.py --steps 200
  (~100M params; use --d-model 256 --steps 30 for a 1-minute demo)
"""
import argparse
import sys

from repro.jax_cache import configure_compile_cache
from repro.launch import train as train_launch


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--d-model", type=int, default=768)
    ap.add_argument("--n-layers", type=int, default=12)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()
    sys.argv = [
        "train", "--arch", "llama3.2-1b",
        "--steps", str(args.steps),
        "--batch", "8", "--seq", "256",
        "--d-model", str(args.d_model), "--n-layers", str(args.n_layers),
        "--microbatches", "2",
        "--ckpt-dir", args.ckpt_dir, "--ckpt-every", "50",
    ]
    train_launch.main()


if __name__ == "__main__":
    configure_compile_cache()
    main()
