"""Seeded synthetic data at the published shapes of each configuration.

Dense rows come from a copy of `repro.data.synthetic`'s
`make_dense_classification`, kept here so that the benchmark's inputs do
not move when the program does; `chipbench/tests/test_gen.py` pins it
bit for bit against the original at a small size.  Sparse rows come from
`make_field_classification`, the benchmark's own: one feature id from
each field, so every row holds exactly its published number of
distinct nonzeros.
"""
from __future__ import annotations

import numpy as np


def _labels_from_logits(rng, logits):
    p = 1.0 / (1.0 + np.exp(-logits))
    return (rng.uniform(size=logits.shape) < p).astype(np.float32) * 2 - 1


def _true_weights(rng, d: int, w_seed):
    """The labelling model's weights: from the row stream, as the
    program's generators draw them, or from a seed of their own."""
    if w_seed is not None:
        rng = np.random.default_rng(w_seed)
    return rng.standard_normal(d).astype(np.float32)


def make_dense_classification(n: int, d: int, *, seed: int,
                              scale: float = 1.0, normalize: bool = True,
                              w_seed: int | None = None):
    """Dense rows of unit norm. X: (d, n) float32, y in {-1, +1}."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((d, n)).astype(np.float32) * scale
    if normalize:
        X /= np.maximum(np.linalg.norm(X, axis=0, keepdims=True), 1e-12)
    w = _true_weights(rng, d, w_seed)
    y = _labels_from_logits(rng, 4.0 * (w @ X) / np.linalg.norm(w))
    return X, y.astype(np.float32)


def field_bounds(d: int, fields: int) -> np.ndarray:
    """Field f owns the feature ids [bounds[f], bounds[f + 1])."""
    return np.arange(fields + 1, dtype=np.int64) * d // fields


def make_field_classification(n: int, d: int, *, fields: int, seed: int,
                              skew: float = 0.0,
                              w_seed: int | None = None):
    """Rows of one nonzero a field: (idx (n, fields) int32, val (n, fields)
    float32), y in {-1, +1}.

    The `fields` fields own disjoint, equal ranges of the `d` feature ids,
    so the ids of a row are distinct.  Inside its field a row's id
    follows a Zipf law of exponent `skew` (uniform where it is 0), the
    field's first id being the most popular.
    """
    rng = np.random.default_rng(seed)
    bounds = field_bounds(d, fields)
    idx = np.empty((n, fields), np.int32)
    for f in range(fields):
        size = int(bounds[f + 1] - bounds[f])
        if skew > 0:
            cdf = np.cumsum(1.0 / np.arange(1, size + 1) ** skew)
            rank = np.searchsorted(cdf / cdf[-1], rng.uniform(size=n),
                                   side="right")
            rank = np.minimum(rank, size - 1)
        else:
            rank = rng.integers(0, size, size=n)
        idx[:, f] = bounds[f] + rank
    val = (rng.standard_normal((n, fields)) / np.sqrt(fields)).astype(
        np.float32)
    w = _true_weights(rng, d, w_seed)
    logits = (val * w[idx]).sum(axis=1) * 4.0
    y = _labels_from_logits(rng, logits)
    return (idx, val), y.astype(np.float32)


def make_data(config: dict, n: int, seed: int):
    """The inputs of one run: a dict with `y` and either `X` (d, n) or
    `idx`/`val` (n, stored width), drawn from `seed` at the
    configuration's shapes.  The labelling model is the configuration's
    own (`w_seed`): every seed draws another sample of the same problem,
    so its solves take the same work."""
    w_seed = config["w_seed"]
    if config["kind"] == "sparse":
        (idx, val), y = make_field_classification(
            n, config["d"], fields=config["nnz"], seed=seed,
            skew=config["skew"], w_seed=w_seed)
        # pad each row with zero-valued entries (feature 0) to the
        # stored width the kernel needs, as an ingest with
        # nnz_multiple does; a zero never moves v or a margin
        m = config.get("nnz_multiple", 1)
        pad = -config["nnz"] % m
        if pad:
            idx = np.pad(idx, ((0, 0), (0, pad)))
            val = np.pad(val, ((0, 0), (0, pad)))
        return {"idx": idx, "val": val, "y": y}
    X, y = make_dense_classification(n, config["d"], seed=seed,
                                     w_seed=w_seed)
    return {"X": X, "y": y}
