"""End-to-end driver: federated pretraining of a ~100M-param LM.

Two FL islands train a granite-family decoder on disjoint token streams,
exchanging weights every 5 steps through the Tier-B mixing collective,
with checkpoints + straggler-aware selection -- the production train loop
at CPU-runnable scale.

Defaults are CPU-friendly (~10M params, 60 steps, minutes); pass
--hundred-m for the full ~100M/300-step run (same code path, longer).

  PYTHONPATH=src python examples/train_lm_federated.py
  PYTHONPATH=src python examples/train_lm_federated.py --hundred-m
"""
import argparse
import dataclasses
from pathlib import Path

from repro.configs import get_smoke_config
from repro.configs.granite_20b import HUNDRED_M
from repro.launch import train

CKPT_DIR = Path(__file__).resolve().parents[1] / "artifacts" / "flight_lm_ckpt"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--hundred-m", action="store_true")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    if args.hundred_m:
        cfg = HUNDRED_M                 # ~100M: 12L x 768 x 12H, 32k vocab
        steps = args.steps or 300
        batch, seq = 8, 256
    else:
        cfg = dataclasses.replace(
            get_smoke_config("granite-20b"),
            num_layers=6, d_model=256, num_heads=8, num_kv_heads=2,
            head_dim=32, d_ff=1024, vocab_size=8_192)
        steps = args.steps or 60
        batch, seq = 8, 128

    argv = ["--steps", str(steps), "--islands", "2", "--local-steps", "5",
            "--batch", str(batch), "--seq", str(seq),
            "--ckpt-dir", str(CKPT_DIR), "--ckpt-every", "25"]
    if args.resume:
        argv.append("--resume")
    train.run(train.parse_args(argv), cfg=cfg)


if __name__ == "__main__":
    main()
