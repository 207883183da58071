"""GLM objectives for SDCA.

Primal:  min_w  P(w) = (1/n) sum_i phi(x_i^T w, y_i) + (lam/2) ||w||^2
Dual:    max_a  D(a) = -(1/n) sum_i phi*(-a_i, y_i) - (lam/2) ||v||^2
with the shared vector v = (1/(lam*n)) * A @ a  (A = [x_1 ... x_n], d x n)
and w = v at optimality.

Each objective provides the scalar dual coordinate update

    delta(m, a, y, q) = argmin_d  phi*(-(a+d), y) + m*d + (q/2) d^2

where m = x_i^T v_local is the current margin and q = sigma' * ||x_i||^2
/ (lam*n) is the (CoCoA-scaled) curvature.  Ridge and hinge solve it in
closed form; logistic by NEWTON_STEPS guarded Newton steps in the logit
of b = (a+d) y, the same float32 ops on every route, so a kernel and
the scan it is pinned to agree bit for bit when their inputs do.  All
functions are elementwise/vectorized and jit/vmap/scan-safe.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax
import jax.numpy as jnp

Array = jax.Array

_EPS = 1e-12
# logistic dual: b = a*y is kept in [_B_LO, _B_HI], t = logit(b) in
# [-_T_MAX, _T_MAX]; NEWTON_STEPS evaluations of h per coordinate
_B_LO = 1e-6
_B_HI = 1.0 - 1e-6
_T_MAX = math.log(_B_HI / _B_LO)
NEWTON_STEPS = 8


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


def _logit_bracket(c: Array, q: Array, b0: Array):
    """First iterate and bracket [lo, hi] of the root of h (see
    `_log_delta`).  sigma(t) - b0 lies in (-b0, 1 - b0), so the root
    lies in [-c - q (1 - b0), -c + q b0], cut to the domain; the
    iteration starts at its midpoint."""
    lo = jnp.clip(-c - q * (1.0 - b0), -_T_MAX, _T_MAX)
    hi = jnp.clip(-c + q * b0, -_T_MAX, _T_MAX)
    return 0.5 * (lo + hi), lo, hi


def _logit_newton(t: Array, c: Array, q: Array, b0: Array):
    """s = sigma(t), h(t) and the Newton step h / h' at t."""
    s = 1.0 / (1.0 + jnp.exp(-t))
    h = t + c + q * (s - b0)
    return s, h, h / (1.0 + q * s * (1.0 - s))


def _logit_step(t: Array, lo: Array, hi: Array, c: Array, q: Array,
                b0: Array):
    """One guarded Newton step: returns (t, lo, hi, guarded).

    h is convex for t < 0 and concave for t > 0, so a Newton step that
    starts on the root's side of 0 and moves toward the root never
    passes it.  The guard: a step that would cross 0 stops there, and
    one that leaves the bracket takes its midpoint."""
    _, h, dt = _logit_newton(t, c, q, b0)
    lo = jnp.where(h < 0.0, t, lo)
    hi = jnp.where(h > 0.0, t, hi)
    tn = t - dt
    cross = tn * t < 0.0
    tn = jnp.where(cross, 0.0, tn)
    out = (tn < lo) | (tn > hi)
    return jnp.where(out, 0.5 * (lo + hi), tn), lo, hi, cross | out


def _logit_update(t: Array, c: Array, q: Array, b0: Array,
                  y: Array) -> Array:
    """d from the last iterate, its Newton step taken in b
    (b = s - s (1-s) h/h'): that keeps the precision which rounding t
    to float32 would lose where b is near 0 or 1."""
    s, _, dt = _logit_newton(t, c, q, b0)
    b = jnp.clip(s - s * (1.0 - s) * dt, _B_LO, _B_HI)
    return (b - b0) * y


def _log_delta(m: Array, a: Array, y: Array, q: Array) -> Array:
    """Guarded Newton in the logit t = logit(b), b = (a+d) y.

    g'(d) = y logit(b) + m + q d is strictly increasing in d; times y
    it is  h(t) = t + y m + q (sigma(t) - b0)  with  h' = 1 + q s (1-s)
    in [1, 1 + q/4], so h is nearly the identity when q is small and
    Newton reaches the float32 root in two or three steps.  The bracket
    and the guard keep it convergent for any q > 0.  NEWTON_STEPS
    evaluations of h, the last one in `_logit_update`, unrolled: one
    static chain inside the kernels' per-example loop.
    """
    b0 = a * y
    c = y * m
    t, lo, hi = _logit_bracket(c, q, b0)
    for _ in range(NEWTON_STEPS - 1):
        t, lo, hi, _ = _logit_step(t, lo, hi, c, q, b0)
    return _logit_update(t, c, q, b0, y)


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
