"""SDCA primitives: bucket recursion and per-worker local sub-epochs.

The TPU formulation of the paper's bucket (DESIGN.md S2): a bucket of B
consecutive coordinates is processed through its Gram matrix

    m0 = X_b^T v          (B,)    margins at bucket entry
    G  = X_b^T X_b        (B,B)

after which the sequential SDCA recursion over the bucket only touches
(m, G, alpha_b, y_b) — O(B^2) scalar work — and the shared vector is
updated once per bucket:  v += (sigma'/lam_n) X_b @ delta.  This is
EXACTLY sequential SDCA in the same visiting order (the in-bucket margin
evolution is fully determined by G), but it
  * streams the (d x B) tile from HBM once,
  * turns the dot/axpy stream into two MXU matmuls + one small recursion,
  * needs one model-axis psum per bucket instead of one per coordinate
    when features are sharded (TP).

sigma' is the CoCoA(+) subproblem scaling: 1 for a truly sequential
solver, K (#independent workers whose updates are summed) for safe
additive aggregation, and deliberately 1-with-summing for the "wild"
simulator (which is what makes it diverge on dense data, as in Fig 1a).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .objectives import Objective

Array = jax.Array

#: f32 products on every backend: TPU's default f32 matmul runs one
#: bf16 pass, which would make the XLA route coarser than the kernels
#: (CPU computes f32 either way).
_HIGHEST = jax.lax.Precision.HIGHEST


def bucket_solve(obj: Objective, G: Array, m0: Array, a0: Array, y: Array,
                 lam_n: Array, sigma_p: Array) -> Array:
    """Sequential SDCA over one bucket via its Gram matrix.

    Returns delta (B,) such that alpha_bucket += delta reproduces the
    sequential visiting order 0..B-1 exactly.
    """
    B = m0.shape[0]

    def body(i, carry):
        m, deltas = carry
        q = sigma_p * jnp.diag(G)[i] / lam_n
        d = obj.delta(m[i], a0[i], y[i], q)
        m = m + (sigma_p * d / lam_n) * G[i]
        deltas = deltas.at[i].set(d)
        return m, deltas

    _, deltas = jax.lax.fori_loop(
        0, B, body, (m0, jnp.zeros_like(m0)))
    return deltas


def dense_local_subepoch(
    obj: Objective,
    Xl: Array,            # (d_shard, n_local) columns in visiting order
    yl: Array,            # (n_local,)
    al: Array,            # (n_local,)
    v0: Array,            # (d_shard,) worker-local replica (model shard)
    lam_n: Array,
    sigma_p: Array,
    bucket: int,
    model_axis: Optional[str] = None,
) -> tuple[Array, Array]:
    """One worker's pass over its buckets.  Returns (al_new, dv).

    When features are sharded over a mesh axis (TP), pass model_axis: the
    per-bucket Gram/margin partials are psum'd so every shard runs the
    identical recursion; v stays shard-local.
    """
    d, n_local = Xl.shape
    nb = n_local // bucket
    Xb = Xl.reshape(d, nb, bucket).transpose(1, 0, 2)   # (nb, d, B)
    ab = al.reshape(nb, bucket)
    yb = yl.reshape(nb, bucket)

    def step(v, inp):
        Xt, a_b, y_b = inp
        m0 = jnp.dot(Xt.T, v, precision=_HIGHEST)          # (B,)
        G = jnp.dot(Xt.T, Xt, precision=_HIGHEST)          # (B,B)
        if model_axis is not None:
            # one fused psum per bucket amortizes the TP collective over B
            # coordinates (vs one per coordinate without bucketing)
            packed = jnp.concatenate([m0[:, None], G], axis=1)
            packed = jax.lax.psum(packed, model_axis)
            m0, G = packed[:, 0], packed[:, 1:]
        deltas = bucket_solve(obj, G, m0, a_b, y_b, lam_n, sigma_p)
        v = v + (sigma_p / lam_n) * jnp.dot(Xt, deltas, precision=_HIGHEST)
        return v, a_b + deltas

    v1, a_new = jax.lax.scan(step, v0, (Xb, ab, yb))
    # CoCoA+: the local replica evolves with the sigma'-scaled updates, but
    # the aggregated global delta is the UNSCALED (1/lam_n) A_k @ dalpha_k.
    return a_new.reshape(-1), (v1 - v0) / sigma_p


def row_sq_norms(val: Array) -> Array:
    """Per-row curvature sum(val**2, axis=-1), added column by column
    from the left.

    The order is fixed on purpose: XLA vectorizes a row reduction
    differently for different batch shapes (a vmapped simulator worker
    vs one mesh lane), and on FMA/AVX-512 hosts the two results then
    differ in the last bit.  The scan below and both sparse kernel
    wrappers take q from here, so every path sees the same bits.
    """
    sq = val * val
    return jax.lax.scan(lambda acc, col: (acc + col, None),
                        jnp.zeros(sq.shape[:-1], sq.dtype),
                        jnp.moveaxis(sq, -1, 0))[0]


def sparse_local_subepoch(
    obj: Objective,
    idx: Array,           # (n_local, nnz) int32 feature ids (padded)
    val: Array,           # (n_local, nnz) values (0 where padded)
    yl: Array,
    al: Array,
    v0: Array,            # (d,) replicated feature vector
    lam_n: Array,
    sigma_p: Array,
) -> tuple[Array, Array]:
    """Sparse (padded-CSR) sequential pass: gather/scatter per coordinate.

    No Gram trick (sparse-sparse Gram is not worth it on the VPU); the
    bucket optimization still applies upstream as shuffle granularity.
    This is the XLA reference path; on TPU the engine routes sparse
    sub-epochs through `kernels.ops.sdca_sparse_bucket_subepoch`, which
    keeps v VMEM-resident and is bitwise-identical to this scan for
    rows obeying the CSR no-duplicate-nonzero invariant (DESIGN.md S11).
    """
    qii = row_sq_norms(val)                             # (n_local,)

    def step(v, inp):
        ii, vv, y, a, q = inp
        m = jnp.sum(v[ii] * vv)
        d = obj.delta(m, a, y, sigma_p * q / lam_n)
        v = v.at[ii].add((sigma_p * d / lam_n) * vv)
        return v, a + d

    v1, a_new = jax.lax.scan(step, v0, (idx, val, yl, al, qii))
    return a_new, (v1 - v0) / sigma_p


def sequential_epoch(
    obj: Objective,
    X: Array,             # (d, n)
    y: Array,
    alpha: Array,
    v: Array,
    lam: float,
    perm: Array,          # (n,) visiting order
    bucket: int = 1,
    sigma_p: float = 1.0,
) -> tuple[Array, Array]:
    """Single-worker epoch (the paper's sequential baseline).

    bucket=1 reproduces classic per-coordinate SDCA; bucket>1 uses the
    Gram recursion (identical updates for the same perm).
    """
    n = y.shape[0]
    lam_n = jnp.asarray(lam * n, X.dtype)
    Xp = X[:, perm]
    a_new, dv = dense_local_subepoch(
        obj, Xp, y[perm], alpha[perm], v, lam_n,
        jnp.asarray(sigma_p, X.dtype), bucket)
    alpha = alpha.at[perm].set(a_new)
    return alpha, v + dv
