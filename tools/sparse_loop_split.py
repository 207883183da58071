#!/usr/bin/env python3
"""Where the sparse kernel's time goes, loop by loop, on a TPU.

    PYTHONPATH=src python3 tools/sparse_loop_split.py --label change

Times `sdca_sparse_bucket_kernel` on criteo-kaggle's rows (the cell's
generator: d = 1,000,000, 39 ids a row from Zipf-1.1 fields, padded to
40; bucket 8) whole, then with each of its three nnz loops emptied in
turn, then with all three emptied:

- `gather`: `_gather_rows` fills W with zeros instead of reading v;
- `alias`: `_bucket_recursion` keeps the margin, the dual update and
  the update row but adds nothing into W;
- `scatter`: `_scatter_rows` (the add of U into v) or `_write_back`
  (the store of W into v), whichever the kernel has, does nothing.

A loop's share is the whole kernel's time less the time without it, so
the shares need not sum to the whole where loops overlap.  The script
patches the kernel module's helpers by name and so runs on any tree
with these helpers: point PYTHONPATH at that tree's `src`.  The
emptied kernels compute wrong numbers; only their times are read.

Prints one JSON line of microseconds per example (the best of
`--reps` calls, each over `--rows` rows) and writes it to `--out`.
Needs a TPU; `--interpret` runs the same steps in Pallas interpret mode
on the CPU at a small size to rehearse them, and times nothing.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402

from chipbench.gen import make_field_classification  # noqa: E402
from repro.core.objectives import LOGISTIC  # noqa: E402
from repro.kernels import sdca_sparse_bucket as ssb  # noqa: E402

D, FIELDS, NNZ, B, SKEW = 1_000_000, 39, 40, 8, 1.1


def _no_gather(idx_s, w_ref, read):
    w_ref[...] = jnp.zeros(w_ref.shape, jnp.float32)


def _no_alias(obj, idx_s, idx, val_ref, y, a0, qrow, lam_n, sig, w_ref,
              *u_ref):
    """The recursion's per-row work without the alias pass; stores the
    update row where the kernel keeps one (a U scratch)."""
    B_, _ = w_ref.shape
    lanes_b = jax.lax.broadcasted_iota(jnp.int32, (1, B_), 1)

    def body(i, deltas):
        vi = val_ref[pl.ds(i, 1), :].astype(jnp.float32)
        m = jnp.sum(w_ref[pl.ds(i, 1), :] * vi, axis=1, keepdims=True)
        d = obj.delta(m, ssb._lane(a0, i), ssb._lane(y, i),
                      sig * ssb._lane(qrow, i) / lam_n)
        u = (sig * d / lam_n) * vi
        if u_ref:
            u_ref[0][pl.ds(i, 1), :] = u
        return jnp.where(lanes_b == i, d, deltas)

    return jax.lax.fori_loop(0, B_, body, jnp.zeros((1, B_), jnp.float32))


def _no_scatter(*args):
    pass


def _scatter_name() -> str:
    return "_write_back" if hasattr(ssb, "_write_back") else "_scatter_rows"


VARIANTS = {
    "whole": {},
    "no_gather": {"_gather_rows": _no_gather},
    "no_alias": {"_bucket_recursion": _no_alias},
    "no_scatter": {"scatter": _no_scatter},
    "none": {"_gather_rows": _no_gather, "_bucket_recursion": _no_alias,
             "scatter": _no_scatter},
}


def _patched(patch: dict):
    saved = {}
    for name, fn in patch.items():
        name = _scatter_name() if name == "scatter" else name
        saved[name] = getattr(ssb, name)
        setattr(ssb, name, fn)
    return saved


def _data(rows: int, seed: int):
    (idx, val), y = make_field_classification(
        rows, D, fields=FIELDS, seed=seed, skew=SKEW, w_seed=1)
    idx = np.concatenate([idx, np.zeros((rows, NNZ - FIELDS), np.int32)], 1)
    val = np.concatenate([val, np.zeros((rows, NNZ - FIELDS), np.float32)], 1)
    nb = rows // B
    q = (val.astype(np.float32) ** 2).sum(1)
    rows_v = ssb.v_pad(D) // ssb.LANES
    return (jnp.asarray(idx.reshape(nb, B, NNZ)),
            jnp.asarray(val.reshape(nb, B, NNZ)),
            jnp.asarray(y.reshape(nb, B)), jnp.zeros((nb, B), jnp.float32),
            jnp.asarray(q.reshape(nb, B)),
            jnp.zeros((rows_v, ssb.LANES), jnp.float32),
            jnp.asarray([1e-3 * rows, 1.0], jnp.float32))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--label", default="tree")
    ap.add_argument("--rows", type=int, default=65_536)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=2_400_000_021)
    ap.add_argument("--out", default=None)
    ap.add_argument("--interpret", action="store_true")
    args = ap.parse_args()
    if not args.interpret and jax.default_backend() != "tpu":
        print("no TPU: a split needs the chip (--interpret rehearses)",
              file=sys.stderr)
        return 2
    rows = 64 if args.interpret else args.rows
    data = _data(rows, args.seed)
    out = {"label": args.label, "rows": rows, "nnz": NNZ, "bucket": B,
           "device": str(jax.devices()[0].device_kind),
           "us_per_example": {}}
    for name, patch in VARIANTS.items():
        saved = _patched(patch)
        jax.clear_caches()
        try:
            run = lambda: jax.block_until_ready(ssb.sdca_sparse_bucket_kernel(
                LOGISTIC, *data, args.interpret, "loop split"))
            run()
            best = float("inf")
            for _ in range(1 if args.interpret else args.reps):
                t0 = time.perf_counter()
                run()
                best = min(best, time.perf_counter() - t0)
        finally:
            _patched(saved)
        out["us_per_example"][name] = (
            None if args.interpret else best / rows * 1e6)
    line = json.dumps(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
