"""End-to-end driver: train a reduced llama3.2-family model for a few
hundred steps on CPU with the full production substrate — TALP
monitoring, background-prefetch data pipeline, async checkpointing with
restart, straggler detection — then print the TALP report and the loss
curve summary.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""

import argparse
import tempfile

import numpy as np

from repro.configs import smoke_config
from repro.launch.train import train
from repro.optim.adamw import AdamWConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--arch", default="llama3.2-3b")
    args = ap.parse_args()

    cfg = smoke_config(args.arch)
    ckpt = tempfile.mkdtemp(prefix="talp_train_")
    state, history, talp = train(
        cfg,
        steps=args.steps,
        global_batch=8,
        seq_len=128,
        ckpt_dir=ckpt,
        ckpt_every=50,
        talp_sample_every=50,
        opt_cfg=AdamWConfig(lr=1e-3, warmup_steps=20, total_steps=args.steps),
    )
    losses = [h["loss"] for h in history]
    print(f"\nloss: {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"({len(losses)} steps, checkpoints in {ckpt})")
    assert np.isfinite(losses[-1])
    assert losses[-1] < losses[0], "loss must decrease on the synthetic task"
    print("OK: loss decreased; TALP report above.")


if __name__ == "__main__":
    main()
