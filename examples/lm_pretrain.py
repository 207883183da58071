"""End-to-end driver: pretrain a ~100M-param smollm-family model for a
few hundred steps on the synthetic Markov stream, with checkpointing.

    PYTHONPATH=src python examples/lm_pretrain.py [--steps 200]

This is the 'train ~100M model for a few hundred steps' deliverable at
CPU scale: real config, sharded-param init (single device here), AdamW,
deterministic restartable data, checkpoint/resume — the same train()
the production launcher uses on the 512-chip mesh.
"""
import argparse

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.base import ArchConfig
from repro.launch.train import train

# ~100M-param llama-family config.  vocab is kept small (2048) so the
# order-1 Markov stream is learnable within a few hundred CPU steps —
# with a 49k vocab the example would need far more tokens than a CPU
# session allows just to move off the uniform-loss plateau.
CFG_100M = ArchConfig(
    name="smollm-100m", family="dense",
    n_layers=16, d_model=640, n_heads=8, n_kv_heads=4, d_ff=2560,
    vocab=2048, remat=False,
)


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_pretrain")
    args = ap.parse_args()

    n_params = CFG_100M.param_count()
    print(f"model: {CFG_100M.name}  params={n_params/1e6:.1f}M")
    _, _, losses = train(CFG_100M, steps=args.steps, batch=args.batch,
                         seq=args.seq, lr=1e-3, ckpt_dir=args.ckpt_dir,
                         ckpt_every=50)
    k = max(len(losses) // 10, 1)
    first, last = np.mean(losses[:k]), np.mean(losses[-k:])
    print(f"loss: {first:.3f} -> {last:.3f} "
          f"({'LEARNING' if last < first - 0.05 else 'check lr/steps'})")


if __name__ == "__main__":
    main()
