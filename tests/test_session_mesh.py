"""`Session` on resident rows with its lanes on a mesh: one lane a
device, rows re-dealt by all-to-all every epoch (`_place`, the
`launch.glm` epoch with row ids).

One subprocess with 4 forced host devices (repo convention: only launch
entrypoints force device counts) runs every mesh solve once at n = 4,096,
d = 2^14 on the criteo-dp4 cell's generator, 39 distinct ids a row padded
to 40, and prints its readings as JSON; the tests below judge them.
"""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.session import Session, _ResidentEpoch
from repro.core import EngineConfig, engine
from repro.data import make_sparse_classification

REPO = pathlib.Path(__file__).resolve().parents[1]

MESH_SOLVES = """
    import json
    import jax, jax.numpy as jnp, numpy as np
    from chipbench import gen, reference, run
    from repro.api import EarlyStopping, Session
    from repro.api.session import _MeshEpoch, _ResidentEpoch
    from repro.core import EngineConfig, engine, sdca
    from repro.core.objectives import LOGISTIC, dual_value
    from repro.data import make_dense_classification
    from repro.launch.mesh import make_host_mesh

    N, D, K = 4096, 1 << 14, 4
    cell = run.load_cell("criteo-dp4")
    cfgd = dict(cell["config"], n=N, d=D)
    target = float(cell["cell"]["target_gap"])
    limits = {k: float(cell["cell"]["limits"][k]) for k in reference.CHECKS}
    data = gen.make_data(cfgd, N, 2400000003)
    prob = reference.Problem(data, cfgd["lam"], D)
    rows = ((data["idx"], data["val"]), data["y"])
    lam = cfgd["lam"]
    out = {"limits": limits}

    def cfg(**kw):
        return EngineConfig.make(bucket=8, lanes=K, chunks=2,
                                 partition="alltoall", **kw)

    # (1) mesh == stacked sim, bitwise in v and row-ordered alpha
    Xd, yd = make_dense_classification(n=N, d=64, seed=5)
    for kind in ("sparse", "dense"):
        spec = cfg(deterministic=True)
        if kind == "sparse":
            s = Session(*rows, d=D, lam=lam, cfg=spec,
                        mesh=make_host_mesh(data=K))
            rid = np.arange(N, dtype=np.int32)
            blk = engine.SparseBlock(
                jnp.asarray(data["idx"].reshape(1, K, N // K, -1)),
                jnp.asarray(data["val"].reshape(1, K, N // K, -1)),
                jnp.asarray(rid.reshape(1, K, -1)))
            y = data["y"]
        else:
            X, y = np.asarray(Xd), np.asarray(yd)
            s = Session((X, y), lam=lam, cfg=spec,
                        mesh=make_host_mesh(data=K))
            rid = np.arange(N, dtype=np.int32)
            blk = engine.DenseBlock(
                jnp.asarray(X.reshape(64, 1, K, N // K).transpose(1, 2, 0, 3)),
                jnp.asarray(rid.reshape(1, K, -1)))
        assert isinstance(s._epoch_fn, _MeshEpoch)
        ys = jnp.asarray(np.asarray(y).reshape(1, K, -1))
        a = jnp.zeros((1, K, N // K), jnp.float32)
        v = jnp.zeros(s.d, jnp.float32)
        def sim(arrs, ys, a, v, e):
            nb, ys, a, v = engine.sharded_epoch(
                LOGISTIC, spec, engine._sim_coll(spec), type(blk)(*arrs),
                ys, a, v, e, lam=lam, n_total=N, workers=K)
            return tuple(x for x, _ in nb.arrs()), ys, a, v

        ep = jax.jit(sim)
        arrs = tuple(x for x, _ in blk.arrs())
        for e in range(3):
            s.epoch()
            arrs, ys, a, v = ep(arrs, ys, a, v, jnp.int32(e))
        a_rows = np.zeros(N, np.float32)
        a_rows[np.asarray(arrs[-1]).ravel()] = np.asarray(a).ravel()
        out[kind + "_v_bitwise"] = bool(np.array_equal(np.asarray(s.v),
                                                       np.asarray(v)))
        out[kind + "_alpha_bitwise"] = bool(np.array_equal(
            np.asarray(s.alpha), a_rows))
        out[kind + "_moved"] = bool(not np.array_equal(
            np.asarray(s._rid), np.arange(N)))

    # (2) state_dict -> load_state_dict in the middle of a solve
    ref = Session(*rows, d=D, lam=lam, cfg=cfg())
    ref.fit(max_epochs=3, tol=0.0)
    s = Session(*rows, d=D, lam=lam, cfg=cfg())
    s.fit(max_epochs=1, tol=0.0)
    st = s.state_dict()
    layout, rid = np.asarray(s._alpha), np.asarray(s._rid)
    by_row = np.zeros(N, np.float32)
    by_row[rid] = layout
    out["state_alpha_in_row_order"] = bool(
        np.array_equal(st["alpha"], by_row)
        and not np.array_equal(st["alpha"], layout))
    s.load_state_dict(st)
    res = s.fit(max_epochs=2, tol=0.0)
    out["round_trip_v"] = bool(np.array_equal(res.v, np.asarray(ref.v)))
    out["round_trip_alpha"] = bool(np.array_equal(res.alpha,
                                                  np.asarray(ref.alpha)))

    # (3) the solve, judged by the benchmark's reference
    s = Session(*rows, d=D, lam=lam, cfg=cfg())
    res = s.fit(max_epochs=30, tol=0.0, callbacks=[
        EarlyStopping(monitor="gap", threshold=target)])
    out["mesh_epochs"] = res.epochs
    out["mesh_gap"] = res.history[-1]["gap"]
    out["checks"] = reference.check_solve(prob, res.v, res.alpha)

    # (4) the sequential solver (core/sdca.py) on the same rows
    idx, val = jnp.asarray(data["idx"]), jnp.asarray(data["val"])
    yj = jnp.asarray(data["y"])
    seq = jax.jit(lambda a, v, p: sdca.sparse_local_subepoch(
        LOGISTIC, idx[p], val[p], yj[p], a[p], v,
        jnp.float32(lam * N), jnp.float32(1.0)))
    a, v = jnp.zeros(N, jnp.float32), jnp.zeros(D, jnp.float32)
    key = jax.random.PRNGKey(0)
    for e in range(1, 31):
        p = jax.random.permutation(jax.random.fold_in(key, e), N)
        a_new, dv = seq(a, v, p)
        a, v = a.at[p].set(a_new), v + dv
        m = jnp.sum(v[idx] * val, axis=1)
        gap = float(jnp.sum(LOGISTIC.loss(m, yj)) / N
                    + 0.5 * lam * jnp.sum(v * v)
                    - dual_value(LOGISTIC, a, v, yj, lam))
        if gap < target:
            break
    out["sequential_epochs"] = e

    # (5) a lane sum that drops lane 0's dv
    lane_sum = engine.MeshCollectives.lane_sum

    def dropped(self, dv, compress=False):
        keep = jax.lax.axis_index("data") != 0
        return lane_sum(self, dv * keep.astype(dv.dtype), compress)

    engine.MeshCollectives.lane_sum = dropped
    s = Session(*rows, d=D, lam=lam, cfg=cfg())
    res = s.fit(max_epochs=3, tol=0.0)
    engine.MeshCollectives.lane_sum = lane_sum
    out["dropped_lane_checks"] = reference.check_solve(prob, res.v,
                                                       res.alpha)

    # (6) lanes above the device count: the simulator, as today
    s = Session(*rows, d=D, lam=lam, cfg=EngineConfig.make(
        bucket=8, lanes=8, chunks=2, partition="alltoall"))
    out["lanes_above_devices_sim"] = (isinstance(s._epoch_fn, _ResidentEpoch)
                                      and s._mesh is None)
    print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_run():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(REPO), str(REPO / "src")]))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(MESH_SOLVES)],
                       capture_output=True, text=True, env=env, timeout=600,
                       cwd=REPO)
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    assert line, r.stdout + r.stderr
    return json.loads(line[-1][len("RESULT "):])


@pytest.mark.parametrize("kind", ["sparse", "dense"])
def test_resident_mesh_matches_the_stacked_sim(mesh_run, kind):
    """deterministic=True, partition="alltoall": three epochs of the
    mesh Session equal `engine.sharded_epoch` on stacked sim lanes, in v
    and in alpha read back in row order, though the rows moved."""
    assert mesh_run[kind + "_moved"]
    assert mesh_run[kind + "_v_bitwise"]
    assert mesh_run[kind + "_alpha_bitwise"]


def test_resident_mesh_state_round_trip_continues_bitwise(mesh_run):
    assert mesh_run["round_trip_v"] and mesh_run["round_trip_alpha"]
    assert mesh_run["state_alpha_in_row_order"]


def test_resident_mesh_solve_passes_the_reference_check(mesh_run):
    limits = mesh_run["limits"]
    assert mesh_run["mesh_gap"] < limits["gap"]
    for k, got in mesh_run["checks"].items():
        assert got < limits[k], (k, got)


def test_resident_mesh_reaches_the_sequential_gap_within_an_epoch(
        mesh_run):
    assert mesh_run["mesh_epochs"] <= mesh_run["sequential_epochs"] + 1


def test_a_dropped_lane_sum_fails_v_map(mesh_run):
    v_map = mesh_run["dropped_lane_checks"]["v_map"]
    assert v_map >= 10 * mesh_run["limits"]["v_map"], v_map


def test_lanes_above_the_device_count_keep_the_simulator(mesh_run):
    assert mesh_run["lanes_above_devices_sim"]


def test_one_device_keeps_the_simulator_program():
    """On one device lanes=4 stays simulated, and the epoch program is
    the one the simulator's `sim_epoch_sparse` lowers to."""
    assert jax.device_count() == 1
    (idx, val), y, d = make_sparse_classification(n=1024, d=64, nnz=8,
                                                  seed=0)
    s = Session(((idx, val), y), d=d, lam=1e-2, cfg=EngineConfig.make(
        bucket=8, lanes=4, chunks=2, partition="alltoall"))
    assert isinstance(s._epoch_fn, _ResidentEpoch) and s._mesh is None
    e = jnp.int32(0)
    got = s._epoch_fn.lower(s.alpha, s.v, e).as_text()
    want = jax.jit(lambda a, v, e, idx, val, y: engine.sim_epoch_sparse(
        s.obj, idx, val, y, a, v, s.lam, s.plan, s.bplan, s.spec, e,
        dv_scale_mul=1.0)).lower(s.alpha, s.v, e, s.idx, s.val,
                                 s.y).as_text()
    assert got == want
    assert not s.spec.deployment.lanes_on(1)
