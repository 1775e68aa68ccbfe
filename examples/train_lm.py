"""Train an assigned-architecture LM end to end (fault-tolerant loop,
async checkpoints, deterministic resumable data).

Thin wrapper over the production launcher; smoke-scale by default so it
finishes on the CPU container, full configs behind --no-smoke:

    PYTHONPATH=src python examples/train_lm.py --arch qwen3-32b --steps 60
    PYTHONPATH=src python examples/train_lm.py --arch mamba2-370m \
        --steps 300 --no-smoke     # ~100M-class model, real shapes
"""
import argparse
import sys

from repro.launch.train import main as launch_main
from repro.runtime.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi3-mini-3.8b")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--no-smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/lm_ckpt")
    args = ap.parse_args()
    enable_compile_cache()

    argv = ["--arch", args.arch, "--steps", str(args.steps),
            "--ckpt-dir", args.ckpt_dir, "--ckpt-every", "25"]
    if not args.no_smoke:
        argv.append("--smoke")
    launch_main(argv)


if __name__ == "__main__":
    main()
