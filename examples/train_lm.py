"""End-to-end driver: train a language model with the delegation framework.

Default trains a ~10M-param qwen2.5-family model for 300 steps on CPU with
checkpointing + fault-tolerant resume; --preset 100m scales to ~100M params
(same command on a TPU pod trains the full configs — the code path is
identical, only the mesh and config change).

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 300] [--preset 100m]
"""
import argparse
import sys

from repro.launch.train import main as train_main


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="10m", choices=["10m", "100m"])
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--inject-failure-at", type=int, default=-1)
    args = ap.parse_args()

    if args.preset == "100m":
        extra = ["--d-model", "512", "--n-layers", "8", "--seq", "256",
                 "--batch", "8"]
    else:
        extra = ["--d-model", "192", "--n-layers", "4", "--seq", "128",
                 "--batch", "8"]

    train_main(["--arch", "qwen2.5-3b", "--smoke", "--steps",
                str(args.steps), "--lr", "3e-3", "--ckpt-dir", args.ckpt_dir,
                "--ckpt-every", "50", "--log-every", "20",
                "--inject-failure-at", str(args.inject_failure_at)] + extra)


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    main()
