"""GLM objectives for SDCA.

Primal:  min_w  P(w) = (1/n) sum_i phi(x_i^T w, y_i) + (lam/2) ||w||^2
Dual:    max_a  D(a) = -(1/n) sum_i phi*(-a_i, y_i) - (lam/2) ||v||^2
with the shared vector v = (1/(lam*n)) * A @ a  (A = [x_1 ... x_n], d x n)
and w = v at optimality.

Each objective provides the scalar dual coordinate update

    delta(m, a, y, q) = argmin_d  phi*(-(a+d), y) + m*d + (q/2) d^2

where m = x_i^T v_local is the current margin and q = sigma' * ||x_i||^2
/ (lam*n) is the (CoCoA-scaled) curvature.  All functions are
elementwise/vectorized and jit/vmap/scan-safe.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

Array = jax.Array

_EPS = 1e-12
_BISECT_ITERS = 40


@dataclasses.dataclass(frozen=True)
class Objective:
    """A GLM loss, its conjugate, and its SDCA coordinate update."""

    name: str
    # phi(z, y): per-example primal loss
    loss: Callable[[Array, Array], Array]
    # phi*(-a, y): per-example dual (conjugate) penalty, +inf outside domain
    conj_neg: Callable[[Array, Array], Array]
    # delta(m, a, y, q): scalar dual coordinate update
    delta: Callable[[Array, Array, Array, Array], Array]
    # whether labels live in {-1, +1} (classification) or R (regression)
    classification: bool


# ---------------------------------------------------------------------------
# Ridge regression (squared loss)
# ---------------------------------------------------------------------------

def _ridge_loss(z: Array, y: Array) -> Array:
    return 0.5 * (z - y) ** 2


def _ridge_conj_neg(a: Array, y: Array) -> Array:
    # phi*(u) = u^2/2 + u*y  =>  phi*(-a) = a^2/2 - a*y
    return 0.5 * a ** 2 - a * y


def _ridge_delta(m: Array, a: Array, y: Array, q: Array) -> Array:
    return (y - m - a) / (1.0 + q)


# ---------------------------------------------------------------------------
# Smooth-hinge-free SVM (hinge loss, box-constrained dual)
# ---------------------------------------------------------------------------

def _hinge_loss(z: Array, y: Array) -> Array:
    return jnp.maximum(0.0, 1.0 - y * z)


def _hinge_conj_neg(a: Array, y: Array) -> Array:
    # phi*(-a) = -a*y on the domain a*y in [0, 1]; +inf outside (callers keep
    # iterates feasible so we do not materialize the +inf branch).
    return -a * y


def _hinge_delta(m: Array, a: Array, y: Array, q: Array) -> Array:
    q = jnp.maximum(q, _EPS)
    b_new = jnp.clip(a * y + (1.0 - y * m) / q, 0.0, 1.0)
    return y * b_new - a


# ---------------------------------------------------------------------------
# Logistic regression
# ---------------------------------------------------------------------------

def _log_loss(z: Array, y: Array) -> Array:
    # log(1 + exp(-y z)), numerically stable
    return jnp.logaddexp(0.0, -y * z)


def _xlogx(b: Array) -> Array:
    return jnp.where(b > _EPS, b * jnp.log(jnp.maximum(b, _EPS)), 0.0)


def _log_conj_neg(a: Array, y: Array) -> Array:
    # phi*(-a) = b log b + (1-b) log(1-b) with b = a*y in [0, 1]
    b = a * y
    return _xlogx(b) + _xlogx(1.0 - b)


def _log_delta(m: Array, a: Array, y: Array, q: Array) -> Array:
    """Guarded bisection on the monotone derivative.

    g(d)  = phi*(-(a+d)) + m d + q d^2 / 2,   b = (a+d) y in (0, 1)
    g'(d) = y log(b / (1-b)) + m + q d        (strictly increasing in d)
    """
    b0 = a * y
    # feasible b in [lo, hi]; keep strictly inside for the log (f32-safe)
    blo = jnp.full_like(b0, 1e-6)
    bhi = jnp.full_like(b0, 1.0 - 1e-6)

    def gprime(b):
        d = (b - b0) * y  # since b = (a+d) y and y^2 = 1
        return y * (jnp.log(b) - jnp.log1p(-b)) + m + q * d

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        gp = gprime(mid)
        # g' increasing in d; d increasing in b iff y > 0.  Bisect on b with
        # the sign flip folded in: moving b by +y moves d by +1.
        go_up = (gp * y) < 0.0
        lo = jnp.where(go_up, mid, lo)
        hi = jnp.where(go_up, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, _BISECT_ITERS, body, (blo, bhi))
    b = 0.5 * (lo + hi)
    return (b - b0) * y


RIDGE = Objective("ridge", _ridge_loss, _ridge_conj_neg, _ridge_delta,
                  classification=False)
HINGE = Objective("hinge", _hinge_loss, _hinge_conj_neg, _hinge_delta,
                  classification=True)
LOGISTIC = Objective("logistic", _log_loss, _log_conj_neg, _log_delta,
                     classification=True)

OBJECTIVES = {o.name: o for o in (RIDGE, HINGE, LOGISTIC)}


def get_objective(name: str) -> Objective:
    try:
        return OBJECTIVES[name]
    except KeyError:
        raise ValueError(f"unknown objective {name!r}; have {list(OBJECTIVES)}")


# ---------------------------------------------------------------------------
# Diagnostics
# ---------------------------------------------------------------------------

def primal_value(obj: Objective, v: Array, X: Array, y: Array,
                 lam: float) -> Array:
    """P(v) for dense X of shape (d, n)."""
    margins = jnp.dot(X.T, v, precision=jax.lax.Precision.HIGHEST)
    n = y.shape[0]
    return jnp.sum(obj.loss(margins, y)) / n + 0.5 * lam * jnp.sum(v * v)


def dual_value(obj: Objective, alpha: Array, v: Array, y: Array,
               lam: float) -> Array:
    n = y.shape[0]
    return -jnp.sum(obj.conj_neg(alpha, y)) / n - 0.5 * lam * jnp.sum(v * v)


def duality_gap(obj: Objective, alpha: Array, v: Array, X: Array, y: Array,
                lam: float) -> Array:
    """P(v) - D(alpha); -> 0 at the optimum.  v must equal A@alpha/(lam n)."""
    return (primal_value(obj, v, X, y, lam)
            - dual_value(obj, alpha, v, y, lam))
