"""Solver-engine seams: layered config, solver registry, and the
sim<->mesh backend equivalence the engine refactor exists to pin.

The multi-device tests shell out with 8 forced host devices (repo
convention: only launch entrypoints force device counts)."""
import os
import pathlib
import subprocess
import sys
import textwrap
import types

import jax
import jax.numpy as jnp
import pytest

from repro.core import (AlgoConfig, EngineConfig,
                        SolverConfig, as_engine_config, make_local_solver)
from repro.core.objectives import LOGISTIC

REPO = pathlib.Path(__file__).resolve().parents[1]


def _run(code: str, timeout=600):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=str(REPO / "src"))
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)


# -- config layering --------------------------------------------------------

def test_engine_config_layering_and_make():
    spec = EngineConfig.make(pods=2, lanes=4, bucket=8, chunks=2,
                             compress_pod=True)
    assert spec.deployment.pods == 2 and spec.deployment.lanes == 4
    assert spec.algo.bucket == 8 and spec.algo.chunks == 2
    assert spec.deployment.compress_pod
    assert spec.workers == 8
    assert spec.sigma_prime() == 8.0
    assert spec.sigma_prime(workers=3) == 3.0
    with pytest.raises(TypeError):
        EngineConfig.make(not_a_knob=1)


def test_solver_config_converts_to_engine():
    flat = SolverConfig(pods=2, lanes=8, bucket=16, partition="alltoall",
                        aggregation="wild", use_kernel=True,
                        compress_sync=True, redeal_frac=0.25)
    spec = as_engine_config(flat)
    assert spec.deployment.pods == 2 and spec.deployment.lanes == 8
    assert spec.algo.partition == "alltoall"
    assert spec.algo.local_solver == "pallas"
    assert spec.algo.compress_sync and spec.algo.redeal_frac == 0.25
    # wild: sigma' stays 1 regardless of worker count
    assert spec.sigma_prime() == 1.0
    assert as_engine_config(spec) is spec


def test_engine_config_passthrough_everywhere():
    # EngineConfig is accepted by the legacy epoch_sim signature
    from repro.core import GLMTrainer
    from repro.data import make_dense_classification
    X, y = make_dense_classification(n=512, d=16, seed=0)
    spec = EngineConfig.make(pods=1, lanes=4, bucket=8,
                             partition="dynamic")
    tr = GLMTrainer(X, y, lam=1e-2, cfg=spec)
    res = tr.fit(max_epochs=30, tol=1e-3)
    assert res.converged


# -- local solver registry --------------------------------------------------

def test_local_solver_registry_guards():
    # sparse + pallas is a real solver now (PR 4), and WITH model_lanes
    # the feature-sharded sparse kernel is too (PR 6); a model_axis
    # without model_lanes still means the legacy TP layout, which no
    # pallas path supports, and unknown kinds are rejected
    assert callable(make_local_solver("pallas", LOGISTIC, 1.0, 1.0,
                                      bucket=8, sparse=True))
    assert callable(make_local_solver("pallas", LOGISTIC, 1.0, 1.0,
                                      bucket=8, sparse=True,
                                      model_axis="model", model_lanes=2))
    with pytest.raises(ValueError):
        make_local_solver("pallas", LOGISTIC, 1.0, 1.0, bucket=8,
                          model_axis="model")
    with pytest.raises(ValueError):
        make_local_solver("pallas", LOGISTIC, 1.0, 1.0, bucket=8,
                          sparse=True, model_axis="model")
    with pytest.raises(ValueError):
        make_local_solver("nope", LOGISTIC, 1.0, 1.0, bucket=8)
    with pytest.raises(ValueError):
        make_local_solver("nope", LOGISTIC, 1.0, 1.0, bucket=8,
                          sparse=True)


def test_local_solver_auto_model_axis_falls_back(monkeypatch):
    """On TPU hosts a backend-picked "auto" must keep LEGACY
    feature-sharded (model-axis without model_lanes) launches on the
    previously-working xla route; only an EXPLICIT pallas request
    raises.  With model_lanes the sparse path has a real sharded kernel
    now (PR 6) and routes there instead."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # backend-auto + model_axis: silently xla, not a ValueError.  Pin
    # the actual route via the closure's qualname (the solver can't be
    # CALLED here — the model-axis psum needs a shard_map context):
    for sp, xla_route in ((False, "dense_xla_solver"),
                          (True, "sparse_solver")):
        solver = make_local_solver("auto", LOGISTIC, 1.0, 1.0, bucket=8,
                                   sparse=sp, model_axis="model")
        assert solver.__qualname__.startswith(xla_route)
    # sparse + model_lanes: the sharded-v solver exists, so auto keeps
    # the pallas choice (wrapped in the trace-time misfit fallback)
    assert callable(make_local_solver("auto", LOGISTIC, 1.0, 1.0,
                                      bucket=8, sparse=True,
                                      model_axis="model", model_lanes=2))
    # the explicit xla twin on the sharded layout masks dv to its slice
    solver = make_local_solver("xla", LOGISTIC, 1.0, 1.0, bucket=8,
                               sparse=True, model_axis="model",
                               model_lanes=2)
    assert solver.__qualname__.startswith("sparse_sharded_xla_solver")
    # an explicit pallas request stays loud on the legacy
    # (no-model_lanes) layouts
    with pytest.raises(ValueError, match="feature sharding"):
        make_local_solver("pallas", LOGISTIC, 1.0, 1.0, bucket=8,
                          model_axis="model")
    with pytest.raises(ValueError, match="feature sharding"):
        make_local_solver("pallas", LOGISTIC, 1.0, 1.0, bucket=8,
                          sparse=True, model_axis="model")


def test_local_solver_auto_sparse_workload_fallback(monkeypatch):
    """Backend-picked sparse "auto" routes kernel-unfit workloads
    (misaligned tiles, blown VMEM budgets) to the XLA scan at trace
    time with a warning, instead of raising at epoch build."""
    import numpy as np
    from repro.data import make_sparse_classification

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    auto = make_local_solver("auto", LOGISTIC, 1.6, 1.0, bucket=8,
                             sparse=True, interpret=True)
    xla = make_local_solver("xla", LOGISTIC, 1.6, 1.0, sparse=True)
    (idx, val), y, d = make_sparse_classification(n=16, d=32, nnz=8,
                                                  seed=0)
    # nnz=7 violates the sublane alignment -> falls back, bitwise-xla
    bad = ((jnp.asarray(idx[:, :7]), jnp.asarray(val[:, :7])),
           jnp.asarray(y), jnp.zeros(16), jnp.zeros(d))
    with pytest.warns(UserWarning, match="sparse Pallas"):
        a1, dv1 = auto(*bad)
    a2, dv2 = xla(*bad)
    assert np.array_equal(np.asarray(a1), np.asarray(a2))
    assert np.array_equal(np.asarray(dv1), np.asarray(dv2))
    # aligned tiles keep using the kernel (bitwise contract holds)
    good = ((jnp.asarray(idx), jnp.asarray(val)), jnp.asarray(y),
            jnp.zeros(16), jnp.zeros(d))
    a1, dv1 = auto(*good)
    a2, dv2 = xla(*good)
    assert np.array_equal(np.asarray(a1), np.asarray(a2))
    assert np.array_equal(np.asarray(dv1), np.asarray(dv2))


def test_local_solver_auto_dense_workload_fallback(monkeypatch):
    """Backend-picked dense "auto" routes kernel-unfit workloads (here:
    tiles over the VMEM budget) to the XLA Gram scan at trace time with
    a warning, and keeps the kernel for fitting ones."""
    import numpy as np

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.choice([-1.0, 1.0], 16).astype(np.float32))
    a = jnp.zeros(16)
    auto = make_local_solver("auto", LOGISTIC, 1.6, 1.0, bucket=8,
                             interpret=True)
    # d large enough that the double-buffered (d_pad, B) tile blows the
    # total VMEM budget -> falls back, bitwise-xla
    d_big = 250_000
    Xb = jnp.asarray(rng.standard_normal((d_big, 16)).astype(np.float32))
    xla = make_local_solver("xla", LOGISTIC, 1.6, 1.0, bucket=8)
    with pytest.warns(UserWarning, match="dense Pallas"):
        a1, dv1 = auto(Xb, y, a, jnp.zeros(d_big))
    a2, dv2 = xla(Xb, y, a, jnp.zeros(d_big))
    assert np.array_equal(np.asarray(a1), np.asarray(a2))
    assert np.array_equal(np.asarray(dv1), np.asarray(dv2))
    # a small workload keeps using the kernel (bitwise vs explicit)
    Xs = jnp.asarray(rng.standard_normal((32, 16)).astype(np.float32))
    pallas = make_local_solver("pallas", LOGISTIC, 1.6, 1.0, bucket=8,
                               interpret=True)
    a1, dv1 = auto(Xs, y, a, jnp.zeros(32))
    a2, dv2 = pallas(Xs, y, a, jnp.zeros(32))
    assert np.array_equal(np.asarray(a1), np.asarray(a2))
    assert np.array_equal(np.asarray(dv1), np.asarray(dv2))


def test_local_solver_auto_resolution(monkeypatch):
    """"auto" = backend-dependent: the XLA scan off the TPU, and on a
    TPU backend the kernel, bitwise-identical to an explicit
    local_solver="pallas" where the shapes fit it."""
    import numpy as np
    from repro.data import make_sparse_classification

    (idx, val), y, d = make_sparse_classification(n=16, d=32, nnz=8,
                                                  seed=0)
    args = ((jnp.asarray(idx), jnp.asarray(val)), jnp.asarray(y),
            jnp.zeros(16), jnp.zeros(d))

    def run(kind):
        a, dv = make_local_solver(kind, LOGISTIC, 1.6, 1.0, bucket=8,
                                  sparse=True, interpret=True)(*args)
        return np.asarray(a), np.asarray(dv)

    xla, pallas = run("xla"), run("pallas")
    for got, want in zip(run("auto"), xla):          # CPU test hosts
        assert np.array_equal(got, want)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for got, want in zip(run("auto"), pallas):
        assert np.array_equal(got, want)


def _shape(*dims):
    return types.SimpleNamespace(shape=dims)


@pytest.mark.parametrize("sparse,model_lanes,path,misfit,fit", [
    # nnz=7 is off the sublane tile; nnz=8 fits the replicated kernel
    (True, None, "sparse",
     ((_shape(16, 7), None), _shape(32)),
     ((_shape(16, 8), None), _shape(32))),
    # over two model lanes a d/2 slice past the resident-v budget has
    # no kernel; one sublane past the replicated budget fits the
    # sharded kernel
    (True, 2, "feature-sharded sparse",
     ((_shape(16, 8), None), _shape(8_388_608)),
     ((_shape(16, 8), None), _shape(2_097_160))),
    # (d_pad, B) tiles over the total VMEM budget; a narrow d fits
    (False, None, "dense",
     (_shape(250_000, 16), _shape(250_000)),
     (_shape(32, 16), _shape(32))),
])
def test_auto_fallback_routes_misfits_to_xla(sparse, model_lanes, path,
                                             misfit, fit):
    """The one trace-time fallback asks the plan for the traced shapes'
    route: a misfit runs the XLA solver with the warning naming the
    layout, a fit runs the kernel with no warning."""
    import warnings
    from repro.core import engine

    ran = []
    solve = engine._auto_fallback(
        lambda *a: ran.append("pallas"), lambda *a: ran.append("xla"),
        sparse=sparse, bucket=8, model_lanes=model_lanes, warn_path=path)
    data, v = misfit
    with pytest.warns(UserWarning, match=f"the {path} Pallas kernel"):
        solve(data, None, None, v)
    data, v = fit
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        solve(data, None, None, v)
    assert ran == ["xla", "pallas"]


@pytest.mark.parametrize("sparse,d,nnz,match", [
    (True, 32, 7, "multiple"),                 # off the sublane tile
    (True, 2_097_160, 8, "VMEM budget"),       # v past the resident budget
    (False, 250_000, 0, "budget"),             # dense tiles over budget
])
def test_explicit_pallas_misfit_raises(sparse, d, nnz, match):
    """An explicit local_solver="pallas" keeps the kernel's actionable
    error on a misfit instead of falling back."""
    import numpy as np
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.choice([-1.0, 1.0], 16).astype(np.float32))
    solver = make_local_solver("pallas", LOGISTIC, 1.6, 1.0, bucket=8,
                               sparse=sparse, interpret=True)
    if sparse:
        idx = jnp.tile(jnp.arange(nnz, dtype=jnp.int32), (16, 1))
        data = (idx, jnp.ones((16, nnz), jnp.float32))
    else:
        data = jnp.zeros((d, 16), jnp.float32)
    with pytest.raises(ValueError, match=match):
        solver(data, y, jnp.zeros(16), jnp.zeros(d))


def test_chunks_must_divide_buckets():
    from repro.core import DenseBlock, SimCollectives, run_epoch
    coll = SimCollectives(pods=1, lanes=2)
    solver = make_local_solver("xla", LOGISTIC, 1.0, 2.0, bucket=8)
    algo = AlgoConfig(bucket=8, chunks=3)
    X = jnp.zeros((2, 2, 4, 64))
    y = jnp.ones((2, 2, 64))
    with pytest.raises(ValueError, match="chunks"):
        run_epoch(coll, solver, algo, DenseBlock(X), y,
                  jnp.zeros((2, 2, 64)), jnp.zeros(4), 0)


# -- sim <-> mesh equivalence (the refactor's contract) ---------------------

def test_sim_mesh_bitwise_equivalence_dense():
    """engine + SimCollectives and engine + MeshCollectives (1 pod x 8
    data lanes, CPU) produce bitwise-identical (alpha, v) after 2
    epochs on a dense workload (deterministic collectives)."""
    r = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import engine
        from repro.core.objectives import LOGISTIC
        from repro.launch.glm import GLMScale, make_dense_epoch
        from repro.launch.mesh import make_host_mesh
        from repro.data import make_dense_classification

        K = 8; n, d = 1024, 64
        scale = GLMScale("t", "dense", n=n, d=d, bucket=8, chunks=2,
                         lam=1e-2, compress_pod=False,
                         deterministic=True)
        X, y = make_dense_classification(n=n, d=d, seed=0)
        X, y = jnp.asarray(X), jnp.asarray(y)
        a0, v0 = jnp.zeros(n), jnp.zeros(d)

        mesh = make_host_mesh(pod=1, data=K, model=1)
        with mesh:
            ep = jax.jit(make_dense_epoch(scale, mesh))
            Xm, ym, am, vm = X, y, a0, v0
            for e in range(2):
                Xm, ym, am, vm = ep(Xm, ym, am, vm, jnp.int32(e))

        spec = scale.engine_config(mesh)
        Xs = jnp.transpose(X.reshape(d, 1, K, n // K), (1, 2, 0, 3))
        ys, as_ = y.reshape(1, K, -1), a0.reshape(1, K, -1)
        sim = jax.jit(lambda X_, y_, a_, v_, e:
                      engine.sim_sharded_dense_epoch(
                          LOGISTIC, spec, X_, y_, a_, v_, e,
                          lam=scale.lam, n_total=n))
        vs = v0
        for e in range(2):
            Xs, ys, as_, vs = sim(Xs, ys, as_, vs, jnp.int32(e))

        assert np.array_equal(np.asarray(vs), np.asarray(vm))
        assert np.array_equal(np.asarray(as_).reshape(-1),
                              np.asarray(am))
        assert np.array_equal(
            np.transpose(np.asarray(Xs)[0], (1, 0, 2)).reshape(d, n),
            np.asarray(Xm))
        assert float(jnp.max(jnp.abs(vs))) > 0   # actually trained
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_sim_mesh_bitwise_equivalence_sparse():
    """Same contract on a sparse (padded-CSR) workload."""
    r = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import engine
        from repro.core.objectives import LOGISTIC
        from repro.launch.glm import GLMScale, make_sparse_epoch
        from repro.launch.mesh import make_host_mesh
        from repro.data import make_sparse_classification

        K = 8; n, d, nnz = 1024, 256, 8
        scale = GLMScale("s", "sparse", n=n, d=d, nnz=nnz, bucket=8,
                         chunks=2, lam=1e-2, compress_pod=False,
                         deterministic=True)
        (idx, val), y, _ = make_sparse_classification(n=n, d=d, nnz=nnz,
                                                      seed=2)
        idx, val, y = (jnp.asarray(t) for t in (idx, val, y))
        a0, v0 = jnp.zeros(n), jnp.zeros(d)

        mesh = make_host_mesh(pod=1, data=K, model=1)
        with mesh:
            ep = jax.jit(make_sparse_epoch(scale, mesh))
            st = (idx, val, y, a0, v0)
            for e in range(2):
                st = ep(*st, jnp.int32(e))
        im, vm_, ym, am, vvm = st

        spec = scale.engine_config(mesh)
        nl = n // K
        st2 = (idx.reshape(1, K, nl, nnz), val.reshape(1, K, nl, nnz),
               y.reshape(1, K, nl), a0.reshape(1, K, nl), v0)
        sim = jax.jit(lambda i, v_, y_, a_, vv, e:
                      engine.sim_sharded_sparse_epoch(
                          LOGISTIC, spec, i, v_, y_, a_, vv, e,
                          lam=scale.lam, n_total=n))
        for e in range(2):
            st2 = sim(*st2, jnp.int32(e))
        iS, vS, yS, aS, vv = st2

        assert np.array_equal(np.asarray(vv), np.asarray(vvm))
        assert np.array_equal(np.asarray(aS).reshape(-1), np.asarray(am))
        assert np.array_equal(np.asarray(iS).reshape(-1, nnz),
                              np.asarray(im))
        assert float(jnp.max(jnp.abs(vv))) > 0
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_sparse_pallas_solver_resident_and_streamed_bitwise(tmp_path):
    """`local_solver="pallas"` on the SPARSE path is bitwise-identical
    to the XLA gather/scatter scan through the full training loop, on
    both the resident and streamed-from-cache harnesses (the PR-4
    acceptance pin; the kernel-level contract lives in
    tests/test_kernels.py)."""
    import numpy as np
    import warnings
    from repro.core import fit_dataset

    outs: dict[tuple, tuple] = {}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for streamed in (False, True):
            for solver in ("xla", "pallas"):
                cfg = EngineConfig.make(
                    pods=2, lanes=2, bucket=8, chunks=2,
                    partition="hierarchical", deterministic=True,
                    local_solver=solver)
                res = fit_dataset(
                    "synthetic-sparse", cfg=cfg, cache_dir=tmp_path,
                    n=256, d=64, streamed=streamed, max_epochs=2,
                    tol=0.0)
                outs[(streamed, solver)] = (res.alpha, res.v)
    for streamed in (False, True):
        xa, xv = outs[(streamed, "xla")]
        pa, pv = outs[(streamed, "pallas")]
        assert np.array_equal(xa, pa), f"alpha differs (streamed={streamed})"
        assert np.array_equal(xv, pv), f"v differs (streamed={streamed})"
    assert np.abs(outs[(True, "pallas")][1]).max() > 0


def test_sparse_pallas_solver_vmap_path_bitwise():
    """The stacked-sim vmap path (deterministic=False) batches the
    sparse Pallas kernel across virtual workers and still matches XLA
    bitwise (pallas_call's vmap rule extends the grid)."""
    import numpy as np
    import warnings
    from repro.core import fit_dataset

    outs = {}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for solver in ("xla", "pallas"):
            cfg = EngineConfig.make(lanes=4, bucket=8, chunks=2,
                                    partition="dynamic",
                                    local_solver=solver)
            res = fit_dataset("synthetic-sparse", cfg=cfg, n=256, d=64,
                              max_epochs=2, tol=0.0)
            outs[solver] = (res.alpha, res.v)
    assert np.array_equal(outs["xla"][0], outs["pallas"][0])
    assert np.array_equal(outs["xla"][1], outs["pallas"][1])


def test_sparse_pallas_local_solver_on_mesh_path():
    """Sparse `local_solver='pallas'` through launch/glm.py's shard_map
    program is BITWISE-identical to the XLA local solver (deterministic
    collectives; interpret-mode kernel on CPU)."""
    r = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.glm import GLMScale, make_sparse_epoch
        from repro.launch.mesh import make_host_mesh
        from repro.data import make_sparse_classification

        K = 8; n, d, nnz = 1024, 256, 8
        (idx, val), y, _ = make_sparse_classification(n=n, d=d, nnz=nnz,
                                                      seed=2)
        idx, val, y = (jnp.asarray(t) for t in (idx, val, y))
        a0, v0 = jnp.zeros(n), jnp.zeros(d)
        mesh = make_host_mesh(pod=1, data=K, model=1)
        outs = {}
        for solver in ("xla", "pallas"):
            sc = GLMScale("s", "sparse", n=n, d=d, nnz=nnz, bucket=8,
                          chunks=2, lam=1e-2, compress_pod=False,
                          deterministic=True, local_solver=solver)
            with mesh:
                ep = jax.jit(make_sparse_epoch(sc, mesh))
                st = (idx, val, y, a0, v0)
                for e in range(2):
                    st = ep(*st, jnp.int32(e))
            outs[solver] = [np.asarray(t) for t in st]
        for xa, pa in zip(outs["xla"], outs["pallas"]):
            assert np.array_equal(xa, pa)
        assert np.abs(outs["pallas"][4]).max() > 0
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


# -- feature-sharded sparse dispatch + mesh path (PR 6, DESIGN.md S12) ------

@pytest.mark.parametrize("n_local,nnz,d,B,M,route,reason_part", [
    # small d: whole v fits in VMEM — data-parallel replicated kernel,
    # regardless of how many model lanes the mesh has
    (64, 8, 4_096, 8, 1, "pallas-replicated", None),
    (64, 8, 4_096, 8, 4, "pallas-replicated", None),
    # exact VMEM boundary row: d_pad * 4 == V_VMEM_BUDGET_BYTES still
    # fits the replicated kernel (budget is inclusive)
    (64, 8, 2_097_152, 8, 1, "pallas-replicated", None),
    (64, 8, 2_097_152, 8, 4, "pallas-replicated", None),
    # one sublane past the boundary: replicated is out; a 2-lane mesh
    # puts it on the sharded kernel, a 1-lane mesh falls back to xla
    (64, 8, 2_097_160, 8, 1, "xla", "resident-v"),
    (64, 8, 2_097_160, 8, 2, "pallas-sharded", None),
    # 4x the boundary: even the d/2 slice is too wide, but d/8 fits
    (64, 8, 8_388_608, 8, 2, "xla", "slice does not fit"),
    (64, 8, 8_388_608, 8, 8, "pallas-sharded", None),
    # alignment and divisibility misfits beat everything
    (64, 7, 4_096, 8, 4, "xla", "multiples of 8"),
    (12, 8, 4_096, 8, 4, "xla", "divide"),
    # wide tiles: the double-buffered (B, nnz) tiles blow the TOTAL
    # budget for replicated AND sharded alike — sharding v doesn't
    # shrink them
    (512, 2048, 4_096, 512, 2, "xla", "total budget"),
])
def test_sparse_solver_plan_decision_table(n_local, nnz, d, B, M, route,
                                           reason_part):
    """The data-parallel vs feature-parallel dispatcher picks the
    documented route on shape corners, VMEM boundary rows included
    (LightGBM-style selection table — SNIPPETS.md Snippet 3)."""
    from repro.kernels import ops as kops
    got_route, got_reason = kops.sparse_solver_plan(
        n_local, nnz, d, B, model_lanes=M)
    assert got_route == route
    if reason_part is None:
        assert got_reason is None
        # misfit agrees: some kernel fits
        assert kops.sparse_kernel_misfit(n_local, nnz, d, B,
                                         model_lanes=M) is None
    else:
        assert reason_part in got_reason
        assert kops.sparse_kernel_misfit(
            n_local, nnz, d, B, model_lanes=M) == got_reason


def test_sparse_sharded_pallas_on_mesh_bitwise():
    """Feature-sharded sparse `local_solver='pallas'` through
    launch/glm.py on a 2x2 (data x model) mesh is BITWISE-identical to
    the slice-masked XLA scan on the same layout (deterministic
    collectives; interpret-mode kernels on CPU).  d=250 exercises
    uneven slices + sublane padding."""
    r = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.glm import GLMScale, make_sparse_epoch
        from repro.launch.mesh import make_host_mesh
        from repro.data import make_sparse_classification

        n, d, nnz = 256, 250, 8
        (idx, val), y, _ = make_sparse_classification(n=n, d=d, nnz=nnz,
                                                      seed=2)
        idx, val, y = (jnp.asarray(t) for t in (idx, val, y))
        a0, v0 = jnp.zeros(n), jnp.zeros(d)
        mesh = make_host_mesh(pod=1, data=2, model=2)
        outs = {}
        for solver in ("xla", "pallas"):
            sc = GLMScale("s", "sparse", n=n, d=d, nnz=nnz, bucket=8,
                          chunks=2, lam=1e-2, compress_pod=False,
                          deterministic=True, local_solver=solver,
                          feature_shard=True)
            with mesh:
                ep = jax.jit(make_sparse_epoch(sc, mesh, interpret=True))
                st = (idx, val, y, a0, v0)
                for e in range(2):
                    st = ep(*st, jnp.int32(e))
            outs[solver] = [np.asarray(t) for t in st]
        for xa, pa in zip(outs["xla"], outs["pallas"]):
            assert np.array_equal(xa, pa)
        assert np.abs(outs["pallas"][4]).max() > 0
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_sparse_sharded_auto_acceptance_webspam_scale():
    """The PR-6 acceptance pin: a workload whose d exceeds the
    replicated kernel's resident-v VMEM budget trains through the
    feature-sharded sparse Pallas path on a model-axis mesh, bitwise
    equal to the XLA scan under deterministic=True, with
    local_solver='auto' selecting it WITHOUT env overrides (backend
    patched to 'tpu'; warnings-as-errors pins that auto did not take
    the misfit fallback).  Also pins the layout default: real webspam
    feature-shards, criteo does not."""
    r = _run("""
        import warnings
        import jax, jax.numpy as jnp, numpy as np
        from repro.kernels import ops as kops
        from repro.kernels.sdca_sparse_bucket import V_VMEM_BUDGET_BYTES
        from repro.launch.glm import (GLMScale, make_sparse_epoch,
                                      scale_for_dataset)
        from repro.launch.mesh import make_host_mesh
        from repro.data import make_sparse_classification

        d = V_VMEM_BUDGET_BYTES // 4 + 8    # past the replicated budget
        n, nnz, B = 32, 8, 8
        assert kops.sparse_solver_plan(n, nnz, d, B, model_lanes=2) == \\
            ("pallas-sharded", None)
        assert scale_for_dataset("webspam").feature_shard
        assert not scale_for_dataset("criteo-kaggle-sub").feature_shard

        (idx, val), y, _ = make_sparse_classification(n=n, d=d, nnz=nnz,
                                                      seed=3)
        idx, val, y = (jnp.asarray(t) for t in (idx, val, y))
        a0, v0 = jnp.zeros(n), jnp.zeros(d)
        mesh = make_host_mesh(pod=1, data=2, model=2)
        jax.default_backend = lambda: "tpu"   # auto resolves to pallas
        outs = {}
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            for solver in ("xla", "auto"):
                sc = GLMScale("w", "sparse", n=n, d=d, nnz=nnz, bucket=B,
                              chunks=2, lam=1e-2, compress_pod=False,
                              deterministic=True, local_solver=solver,
                              feature_shard=True)
                with mesh:
                    ep = jax.jit(make_sparse_epoch(sc, mesh,
                                                   interpret=True))
                    st = ep(idx, val, y, a0, v0, jnp.int32(0))
                outs[solver] = [np.asarray(t) for t in st]
        for xa, pa in zip(outs["xla"], outs["auto"]):
            assert np.array_equal(xa, pa)
        assert np.abs(outs["auto"][4]).max() > 0
        print("OK")
    """, timeout=900)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_pallas_local_solver_on_distributed_path():
    """local_solver='pallas' is selectable through launch/glm.py and
    matches the XLA local solver to <=1e-5 after one epoch (interpret
    mode on CPU)."""
    r = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.glm import GLMScale, make_dense_epoch
        from repro.launch.mesh import make_host_mesh
        from repro.data import make_dense_classification

        K = 8; n, d = 1024, 64
        X, y = make_dense_classification(n=n, d=d, seed=0)
        X, y = jnp.asarray(X), jnp.asarray(y)
        a0, v0 = jnp.zeros(n), jnp.zeros(d)
        mesh = make_host_mesh(pod=1, data=K, model=1)
        outs = {}
        for solver in ("xla", "pallas"):
            sc = GLMScale("p", "dense", n=n, d=d, bucket=8, chunks=2,
                          lam=1e-2, compress_pod=False,
                          local_solver=solver)
            with mesh:
                ep = jax.jit(make_dense_epoch(sc, mesh))
                outs[solver] = [np.asarray(t) for t in
                                ep(X, y, a0, v0, jnp.int32(0))]
        for xa, pa in zip(outs["xla"], outs["pallas"]):
            np.testing.assert_allclose(xa, pa, atol=1e-5, rtol=1e-5)
        assert np.abs(outs["pallas"][3]).max() > 0
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr
