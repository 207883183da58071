"""Unit + property tests for the GLM objectives and SDCA scalar update."""
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional test dep
from hypothesis import given, settings, strategies as st

from repro.core import objectives as O
from repro.core.objectives import (HINGE, LOGISTIC, RIDGE, duality_gap,
                                   get_objective)

jax.config.update("jax_enable_x64", False)

OBJS = [RIDGE, HINGE, LOGISTIC]


def _label(obj, rng):
    return (rng.choice([-1.0, 1.0]) if obj.classification
            else float(rng.standard_normal()))


@pytest.mark.parametrize("obj", OBJS, ids=lambda o: o.name)
def test_delta_minimizes_scalar_subproblem(obj):
    """delta = argmin_d phi*(-(a+d)) + m d + q d^2/2 — check vs grid."""
    rng = np.random.default_rng(0)
    for _ in range(50):
        m = float(rng.standard_normal())
        y = _label(obj, rng)
        q = float(rng.uniform(0.05, 5.0))
        if obj.classification:
            b0 = rng.uniform(0.02, 0.98)
            a = float(y * b0)
        else:
            a = float(rng.standard_normal() * 0.3)
        d_star = float(obj.delta(jnp.float32(m), jnp.float32(a),
                                 jnp.float32(y), jnp.float32(q)))

        def g(d):
            return float(obj.conj_neg(jnp.float32(a + d), jnp.float32(y))
                         + m * d + 0.5 * q * d * d)

        g_star = g(d_star)
        # compare against a fine grid around the feasible region
        if obj.classification:
            grid = (np.linspace(1e-4, 1 - 1e-4, 2001) * y - a)
        else:
            grid = np.linspace(d_star - 2.0, d_star + 2.0, 2001)
        g_grid = min(g(d) for d in grid)
        assert g_star <= g_grid + 5e-4, (obj.name, g_star, g_grid)


@pytest.mark.parametrize("obj", OBJS, ids=lambda o: o.name)
def test_conjugate_fenchel_young(obj):
    """phi(z) + phi*(-a) = -z*a at a = -phi'(z) (Fenchel-Young)."""
    rng = np.random.default_rng(1)
    for _ in range(100):
        z = float(rng.standard_normal() * 2)
        y = _label(obj, rng)
        if obj.name == "ridge":
            a_opt = -(z - y)
        elif obj.name == "logistic":
            a_opt = y / (1 + np.exp(y * z))
        else:           # hinge: subgradient; test only at z*y < 1 (a=y)
            if y * z >= 1:
                continue
            a_opt = y
        lhs = float(obj.loss(jnp.float32(z), jnp.float32(y))
                    + obj.conj_neg(jnp.float32(a_opt), jnp.float32(y)))
        assert abs(lhs + z * a_opt) < 1e-3, (obj.name, lhs, -z * a_opt)


@given(st.integers(0, 2 ** 31 - 1), st.sampled_from([o.name for o in OBJS]))
@settings(max_examples=30, deadline=None)
def test_weak_duality_property(seed, obj_name):
    """gap = P(v) - D(alpha) >= 0 whenever v = A @ alpha / (lam n)."""
    obj = get_objective(obj_name)
    rng = np.random.default_rng(seed)
    d, n = 5, 32
    lam = 0.1
    X = jnp.asarray(rng.standard_normal((d, n)), jnp.float32)
    y = jnp.asarray(rng.choice([-1.0, 1.0], n) if obj.classification
                    else rng.standard_normal(n), jnp.float32)
    if obj.classification:
        alpha = jnp.asarray(rng.uniform(0.01, 0.99, n), jnp.float32) * y
    else:
        alpha = jnp.asarray(rng.standard_normal(n), jnp.float32)
    v = X @ alpha / (lam * n)
    gap = float(duality_gap(obj, alpha, v, X, y, lam))
    assert gap >= -1e-3, gap


@given(st.floats(-3, 3), st.floats(0.05, 5), st.floats(0.02, 0.98),
       st.sampled_from([-1.0, 1.0]))
@settings(max_examples=100, deadline=None)
def test_delta_keeps_dual_feasible(m, q, b0, y):
    """classification duals must stay in the conjugate domain."""
    for obj in (HINGE, LOGISTIC):
        a = y * b0
        d = float(obj.delta(jnp.float32(m), jnp.float32(a),
                            jnp.float32(y), jnp.float32(q)))
        b_new = (a + d) * y
        assert -1e-5 <= b_new <= 1 + 1e-5, (obj.name, b_new)


def test_get_objective_errors():
    with pytest.raises(ValueError):
        get_objective("nope")


# ---------------------------------------------------------------------------
# The logistic coordinate update: guarded Newton in the logit
# ---------------------------------------------------------------------------

def _bisection40(m, a, y, q):
    """The 40-step float32 bisection on b that the Newton iteration
    replaced, kept here as the accuracy yardstick."""
    b0 = a * y
    lo = jnp.full_like(b0, 1e-6)
    hi = jnp.full_like(b0, 1.0 - 1e-6)
    for _ in range(40):
        mid = 0.5 * (lo + hi)
        gp = (y * (jnp.log(mid) - jnp.log1p(-mid)) + m
              + q * (mid - b0) * y)
        up = gp * y < 0.0
        lo = jnp.where(up, mid, lo)
        hi = jnp.where(up, hi, mid)
    return (0.5 * (lo + hi) - b0) * y


def _argmin64(m, a, y, q):
    """float64 minimiser of the logistic subproblem, b clamped to the
    update's domain [1e-6, 1 - 1e-6]: 200 bisection steps on the
    logit, returned as d."""
    m, a, y, q = (np.asarray(x, np.float64) for x in (m, a, y, q))
    b0 = a * y
    t_max = np.log((1 - 1e-6) / 1e-6)
    lo, hi = np.full_like(b0, -t_max), np.full_like(b0, t_max)
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        h = mid + y * m + q * (1 / (1 + np.exp(-mid)) - b0)
        lo, hi = np.where(h < 0, mid, lo), np.where(h < 0, hi, mid)
    b = np.clip(1 / (1 + np.exp(-0.5 * (lo + hi))), 1e-6, 1 - 1e-6)
    return (b - b0) * y


@pytest.mark.parametrize("q", [1e-5, 1e-4, 4.8e-4, 1e-3, 3.8e-3, 1e-2, 0.1,
                               1.0, 4.0, 10.0, 30.0, 1e2])
def test_logistic_delta_as_accurate_as_bisection(q):
    """LOGISTIC.delta against a float64 minimiser on a grid of m in
    [-30, 30], b0 at both clamp edges, at 0 and 1 and inside, y = +-1:
    its error is no larger than the 40-step bisection's plus 2 ulp.
    The ulp is float32's at b, or at d where d is larger: the update
    returns d, rounded to float32, and d = b - b0 near b0 = 1 can be a
    million times b (the bisection's error there is that rounding).
    Measured (float32 on CPU): largest error 2.07 ulp, the bisection's
    11.6; Newton's is the larger on about 5% of the cases, by at most
    1.22 ulp.  This accuracy takes at most 6 evaluations of h (q = 30
    and 100; 2 for q <= 0.1), so NEWTON_STEPS = 8 leaves two spare."""
    M, B0, Y = np.meshgrid(
        np.linspace(-30, 30, 121),
        [0.0, 1e-6, 1e-3, 0.1, 0.3, 0.5, 0.7, 0.9, 0.999, 1 - 1e-6, 1.0],
        [-1.0, 1.0], indexing="ij")
    m, y = M.ravel().astype(np.float32), Y.ravel().astype(np.float32)
    a = (B0.ravel() * Y.ravel()).astype(np.float32)
    qq = np.full_like(m, q)
    args = [jnp.asarray(x) for x in (m, a, y, qq)]
    d_star = _argmin64(m, a, y, qq)
    err_new = np.abs(np.asarray(jax.jit(LOGISTIC.delta)(*args), np.float64)
                     - d_star)
    err_old = np.abs(np.asarray(jax.jit(_bisection40)(*args), np.float64)
                     - d_star)
    b_star = a.astype(np.float64) * y + d_star * y
    ulp = np.spacing(np.maximum(np.abs(b_star), np.abs(d_star))
                     .astype(np.float32)).astype(np.float64)
    bad = err_new > err_old + 2 * ulp
    assert not bad.any(), list(zip(m[bad], a[bad], y[bad],
                                   err_new[bad] / ulp[bad],
                                   err_old[bad] / ulp[bad]))[:5]


def _solve_inputs(cell: str, n: int, epochs: int, seed: int):
    """(m, a, y, q) of every logistic coordinate update in `epochs`
    sequential SDCA epochs on n rows of `cell`'s generator, with the
    cell's lambda: the inputs the kernels hand LOGISTIC.delta."""
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from chipbench import gen, run
    cfg = run.load_cell(cell)["config"]
    data = gen.make_data(cfg, n, seed=seed)
    lam_n = jnp.float32(cfg["lam"] * n)
    y = jnp.asarray(data["y"])
    if "X" in data:
        rows = (jnp.asarray(data["X"].T),)

        def margin(v, x):
            return jnp.vdot(x, v), jnp.vdot(x, x), x, None
    else:
        rows = (jnp.asarray(data["idx"]), jnp.asarray(data["val"]))

        def margin(v, ii, vv):
            return jnp.sum(v[ii] * vv), jnp.sum(vv * vv), vv, ii

    def step(v, inp):
        *row, yi, ai = inp
        m, sq, vals, ids = margin(v, *row)
        q = sq / lam_n
        d = LOGISTIC.delta(m, ai, yi, q)
        u = (d / lam_n) * vals
        v = v + u if ids is None else v.at[ids].add(u)
        return v, (ai + d, (m, ai, yi, q))

    v = jnp.zeros(cfg["d"], jnp.float32)
    a = jnp.zeros(n, jnp.float32)
    seen = []
    for _ in range(epochs):
        v, (a, inputs) = jax.lax.scan(step, v, (*rows, y, a))
        seen.append(inputs)
    return [jnp.concatenate(x) for x in zip(*seen)]


@pytest.mark.parametrize("cell", ["criteo-1chip", "higgs-1chip"])
def test_logistic_newton_margin_on_a_solve(cell):
    """On the (m, a, y, q) of a three-epoch solve at n = 4096 (q 0.11 to
    0.48 on criteo, 0.24 on higgs: 28 to 500 times the cells' own, so no
    easier), the update settles within NEWTON_STEPS - 2 evaluations of
    h: from there on it stays within 2 float32 ulp of b of where
    3 * NEWTON_STEPS leave it (at the root t may cycle between
    neighbouring floats, since h is known only to its rounding).
    Measured (float32 on CPU, 12,288 updates a cell): at most 3
    evaluations on both cells, 2 or fewer for all but 5 (criteo) and 11
    (higgs) updates; the guard fired on none of the steps taken before
    an update settled."""
    m, a, y, q = _solve_inputs(cell, 4096, 3, seed=2_147_483_659)
    b0, c = a * y, y * m
    t, lo, hi = O._logit_bracket(c, q, b0)
    updates, guards = [], []
    for _ in range(3 * O.NEWTON_STEPS):
        updates.append(np.asarray(O._logit_update(t, c, q, b0, y)))
        t, lo, hi, guarded = O._logit_step(t, lo, hi, c, q, b0)
        guards.append(np.asarray(guarded))
    updates, guards = np.stack(updates), np.stack(guards)
    np.testing.assert_array_equal(
        updates[O.NEWTON_STEPS - 1], np.asarray(LOGISTIC.delta(m, a, y, q)))
    b = np.asarray(b0) + updates[-1] * np.asarray(y)
    settled = (np.abs(updates - updates[-1])
               <= 2 * np.spacing(np.abs(b).astype(np.float32)))
    tail = np.flip(np.cumprod(np.flip(settled, 0), 0), 0).astype(bool)
    evaluations = np.argmax(tail, axis=0) + 1
    assert evaluations.max() <= O.NEWTON_STEPS - 2
    taken = np.arange(len(guards))[:, None] < evaluations - 1
    assert guards[taken].mean() < 0.01
