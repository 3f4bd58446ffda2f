"""End-to-end driver: train a ~100M-param model for a few hundred steps.

This is the deliverable-(b) scale run (CPU-sized batch; the same code,
launch/train.py, runs it on one chip on a TPU host).

    PYTHONPATH=src python examples/train_100m.py --steps 200
"""
import argparse

from repro.launch import train as train_cli


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--arch", default="qwen2-0.5b")
    args = ap.parse_args()
    return train_cli.main([
        "--arch", args.arch,
        "--preset", "100m",
        "--steps", str(args.steps),
        "--batch", "4",
        "--seq", "256",
        "--ckpt", "/tmp/train_100m_ckpt",
        "--ckpt-every", "50",
        "--resume", "auto",
        "--log-every", "10",
    ])


if __name__ == "__main__":
    raise SystemExit(main())
