"""The plain reference: what decides whether a solve is correct.

A solve answers "here are a dual vector alpha and a primal vector v whose
duality gap is below the target".  `check_solve` judges that answer by
what it says, in float64 on the host, from the benchmark's own copy of
the data and nothing the program made:

  w(alpha) = (1 / (lam n)) sum_i alpha_i x_i          (the primal-dual map)
  P(v)     = (1/n) sum_i log(1 + exp(-y_i x_i.v)) + (lam/2) |v|^2
  D(alpha) = -(1/n) sum_i [b_i log b_i + (1-b_i) log(1-b_i)]
             - (lam/2) |w(alpha)|^2,        b_i = alpha_i y_i in [0, 1]

and compares two numbers:

  gap   P(v) - D(alpha): the certificate of the answer, recomputed with
        the dual at w(alpha); the solve stopped on the program's own
        certificate, so this must be below the target too
  v_map |v - w(alpha)| / |w(alpha)|: v is the image of alpha, so every
        update, lane sum and fed row reached both

`solve_reference` is a plain mini-batch SDCA for the same objective in
`jax.numpy`, in a dtype of the caller's choice.  It is the control: put
in the program's place in bfloat16, its answers must fail `check_solve`.
It imports nothing of the program.
"""
from __future__ import annotations

import numpy as np

CHECKS = ("gap", "v_map")


class Problem:
    """One run's data in float64 on the host, with the maps the checker
    needs.  Built once per run and reused for every solve."""

    def __init__(self, data: dict, lam: float, d: int):
        self.lam = float(lam)
        self.d = int(d)
        self.y = data["y"].astype(np.float64)
        self.n = self.y.shape[0]
        self.sparse = "idx" in data
        if self.sparse:
            self.idx = data["idx"]
            self.val = data["val"].astype(np.float64)
        else:
            self.X = data["X"].astype(np.float64)        # (d, n)

    def margins(self, v: np.ndarray) -> np.ndarray:
        if self.sparse:
            return np.einsum("ij,ij->i", v[self.idx], self.val)
        return self.X.T @ v

    def w_of_alpha(self, alpha: np.ndarray) -> np.ndarray:
        if self.sparse:
            w = np.bincount(self.idx.ravel(),
                            weights=(alpha[:, None] * self.val).ravel(),
                            minlength=self.d)
        else:
            w = self.X @ alpha
        return w / (self.lam * self.n)

    def primal(self, v: np.ndarray) -> float:
        loss = np.logaddexp(0.0, -self.y * self.margins(v))
        return float(np.mean(loss) + 0.5 * self.lam * v @ v)

    def dual(self, alpha: np.ndarray) -> tuple[float, np.ndarray]:
        b = alpha * self.y
        if b.min() < 0.0 or b.max() > 1.0:
            return -np.inf, self.w_of_alpha(alpha)
        w = self.w_of_alpha(alpha)
        ent = _xlogx(b) + _xlogx(1.0 - b)
        return float(-np.mean(ent) - 0.5 * self.lam * w @ w), w


def _xlogx(b: np.ndarray) -> np.ndarray:
    return np.where(b > 0.0, b * np.log(np.where(b > 0.0, b, 1.0)), 0.0)


def check_solve(prob: Problem, v, alpha) -> dict:
    """The readings of one solve's answer (see the module doc)."""
    v = np.asarray(v, np.float64)
    alpha = np.asarray(alpha, np.float64)
    if not (np.all(np.isfinite(v)) and np.all(np.isfinite(alpha))):
        return {"gap": np.inf, "v_map": np.inf}
    dual, w = prob.dual(alpha)
    gap = prob.primal(v) - dual
    wn = np.linalg.norm(w)
    v_map = float(np.linalg.norm(v - w) / wn) if wn > 0 else (
        0.0 if not v.any() else np.inf)
    return {"gap": float(gap), "v_map": v_map}


def worst(readings: list[dict]) -> dict:
    """The largest reading of each number over solves."""
    return {k: max(r[k] for r in readings) for k in CHECKS}


def within(readings: dict, limits: dict) -> bool:
    return all(readings[k] <= limits[k] for k in CHECKS)


# -- the control ------------------------------------------------------------

def solve_reference(data: dict, lam: float, d: int, *, dtype, batch: int,
                    target_gap: float, max_epochs: int, seed: int):
    """Plain mini-batch SDCA (logistic loss) in `dtype`.

    Each step takes `batch` examples of a seeded permutation, solves each
    one's dual coordinate against the same v with the curvature scaled by
    `batch` (which keeps the sum of the updates safe), and adds all of
    them to alpha and v.  After every epoch it computes its own gap in
    `dtype` and stops below `target_gap`.  Returns (v, alpha, epochs,
    claimed_gap) as float32/float64 host arrays.
    """
    import jax
    import jax.numpy as jnp

    y = jnp.asarray(data["y"], dtype)
    n = y.shape[0]
    if n % batch:
        raise ValueError(f"batch {batch} must divide n={n}")
    sparse = "idx" in data
    if sparse:
        rows_of = (jnp.asarray(data["idx"]), jnp.asarray(data["val"], dtype))
    else:
        rows_of = (jnp.asarray(data["X"], dtype),)        # (d, n)
    lam_n = lam * n
    eps = float(jnp.finfo(dtype).eps)
    hp = jax.lax.Precision.HIGHEST

    # the data rides in as arguments: arrays a jitted function closes
    # over are compiled into it as constants
    def margin(v, rows, D):
        if sparse:
            idx, val = D
            return jnp.sum(v[idx[rows]] * val[rows], axis=1)
        return jnp.dot(D[0][:, rows].T, v, precision=hp)

    def sq_norms(rows, D):
        if sparse:
            return jnp.sum(D[1][rows] ** 2, axis=1)
        return jnp.sum(D[0][:, rows] ** 2, axis=0)

    def delta(m, a, yb, q):
        # bisection on b = (a + d) y for the 1-D dual problem
        b0 = a * yb

        def body(_, lohi):
            lo, hi = lohi
            mid = 0.5 * (lo + hi)
            g = yb * (jnp.log(mid) - jnp.log1p(-mid)) + m + q * (mid - b0) * yb
            up = g * yb < 0
            return jnp.where(up, mid, lo), jnp.where(up, hi, mid)

        lo = jnp.full_like(b0, eps)
        hi = jnp.full_like(b0, 1.0 - eps)
        lo, hi = jax.lax.fori_loop(0, 30, body, (lo, hi))
        return (0.5 * (lo + hi) - b0) * yb

    @jax.jit
    def epoch(a, v, key, y, D):
        def step(carry, rows):
            a, v = carry
            m = margin(v, rows, D)
            q = (batch / lam_n) * sq_norms(rows, D)
            d = delta(m, a[rows], y[rows], q.astype(dtype))
            a = a.at[rows].add(d)
            if sparse:
                idx, val = D
                v = v.at[idx[rows].ravel()].add(
                    ((d[:, None] * val[rows]) / lam_n).ravel())
            else:
                v = v + jnp.dot(D[0][:, rows], d, precision=hp) / lam_n
            return (a, v), None

        order = jax.random.permutation(key, n).reshape(-1, batch)
        (a, v), _ = jax.lax.scan(step, (a, v), order)
        return a, v

    @jax.jit
    def gap_of(a, v, y, D):
        m = margin(v, jnp.arange(n), D)
        p = jnp.mean(jnp.logaddexp(0.0, -y * m)) + 0.5 * lam * v @ v
        b = jnp.clip(a * y, 0.0, 1.0)
        ent = (jnp.where(b > 0, b * jnp.log(jnp.where(b > 0, b, 1.0)), 0.0)
               + jnp.where(b < 1, (1 - b) * jnp.log(jnp.where(b < 1, 1 - b,
                                                              1.0)), 0.0))
        dv = -jnp.mean(ent) - 0.5 * lam * v @ v
        return p - dv

    a = jnp.zeros(n, dtype)
    v = jnp.zeros(d, dtype)
    key = jax.random.PRNGKey(seed % (2 ** 31))
    claimed = float("inf")
    epochs = 0
    while epochs < max_epochs:
        a, v = epoch(a, v, jax.random.fold_in(key, epochs), y, rows_of)
        epochs += 1
        claimed = float(gap_of(a, v, y, rows_of))
        if claimed < target_gap:
            break
    return (np.asarray(v.astype(jnp.float32)),
            np.asarray(a.astype(jnp.float32)), epochs, claimed)
