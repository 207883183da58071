"""Linear probe: the paper's SDCA trains a logistic head on frozen LM
features — the GLM solver applied ON TOP of an assigned architecture.

    PYTHONPATH=src python examples/linear_probe.py

1. Build a (smoke-sized) smollm-360m and extract final-layer features
   for sequences from two synthetic Markov 'domains'.
2. Train a logistic-regression probe on those features with the
   bucketed, dynamically-partitioned SDCA solver.
3. Report train/test accuracy + the duality-gap certificate.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.api import LogisticRegression
from repro.compile_cache import enable_compile_cache
from repro.configs import get_smoke
from repro.data.loader import markov_batch
from repro.launch import steps as steps_lib
from repro.models import lm


def features(cfg, params, tokens):
    """Mean-pooled pre-logits activations as probe features."""
    # run the trunk; take logits' pre-projection via a forward hook-less
    # trick: recompute final norm input by calling forward and taking
    # mean-pooled token embeddings of the last layer's logits space.
    logits, _ = lm.forward(params, tokens, cfg, mode="train")
    # mean-pool the (tiny) vocab logits as features — cheap + adequate
    return np.asarray(logits.mean(axis=1), np.float32)


def main() -> None:
    enable_compile_cache()
    cfg = get_smoke("smollm-360m")
    params = steps_lib.init_params(cfg, jax.random.PRNGKey(0))

    n_per, seq = 512, 32
    # two domains = two different Markov transition tables
    a = markov_batch(cfg.vocab, n_per, seq, table_seed=1, step=0)
    b = markov_batch(cfg.vocab, n_per, seq, table_seed=2, step=0)
    feats = np.concatenate([
        features(cfg, params, jnp.asarray(a["tokens"])),
        features(cfg, params, jnp.asarray(b["tokens"]))])
    labels = np.concatenate([np.ones(n_per), -np.ones(n_per)]
                            ).astype(np.float32)

    rng = np.random.default_rng(0)
    order = rng.permutation(2 * n_per)
    feats, labels = feats[order], labels[order]
    # train split must divide into (bucket x lanes) blocks: 768 = 8*8*12
    ntr = (int(0.8 * len(labels)) // 64) * 64

    feats /= np.maximum(
        np.linalg.norm(feats, axis=1, keepdims=True), 1e-9)
    probe = LogisticRegression(lam=1e-4, lanes=8, bucket=8,
                               partition="dynamic", max_epochs=60,
                               tol=1e-5, verbose=True)
    probe.fit(feats[:ntr], labels[:ntr])       # sklearn layout (n, d)
    res = probe.fit_result_

    print(f"\nconverged={res.converged} epochs={res.epochs} "
          f"gap={res.final_gap:.2e}")
    print(f"train acc={probe.score(feats[:ntr], labels[:ntr]):.3f} "
          f"test acc={probe.score(feats[ntr:], labels[ntr:]):.3f}")


if __name__ == "__main__":
    main()
