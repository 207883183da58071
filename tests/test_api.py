"""Public API surface: Session epoch control, sklearn-compatible
estimators (+ real-sklearn parity), callbacks, whole-estimator
checkpoint resume, and the legacy-shim deprecation contract."""
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.api import (EarlyStopping, GapLogger, LinearSVC,
                       LogisticRegression, NotFittedError,
                       ReproDeprecationWarning, Ridge, Session)
from repro.api import load as load_estimator
from repro.api.deprecation import reset_deprecation_registry
from repro.core import EngineConfig, SolverConfig
from repro.data import (make_dense_classification,
                        make_sparse_classification, registry)

DET = EngineConfig.make(pods=1, lanes=2, bucket=8, chunks=2,
                        partition="hierarchical", deterministic=True)


def _dense(n=512, d=32, seed=0):
    X, y = make_dense_classification(n=n, d=d, seed=seed)
    return np.asarray(X), np.asarray(y)


# -- Session ----------------------------------------------------------------

def test_session_epoch_and_fit_until_are_reentrant():
    X, y = _dense()
    kw = dict(objective="logistic", lam=1e-2, cfg=DET)
    a = Session((X, y), **kw)
    rec = a.epoch()
    assert rec["epoch"] == 1 and rec["rel_change"] > 0
    a.fit(until=6, tol=0.0)
    assert a.epochs_done == 6

    b = Session((X, y), **kw)
    b.fit(until=3, tol=0.0)
    b.fit(until=6, tol=0.0)
    np.testing.assert_array_equal(np.asarray(a.v), np.asarray(b.v))
    np.testing.assert_array_equal(np.asarray(a.alpha),
                                  np.asarray(b.alpha))
    with pytest.raises(TypeError, match="either"):
        a.fit(until=9, max_epochs=1)


def test_session_matches_legacy_trainer_bitwise():
    from repro.core import GLMTrainer
    X, y = _dense()
    ses = Session((X, y), objective="logistic", lam=1e-2, cfg=DET)
    ses.fit(max_epochs=3, tol=0.0)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", ReproDeprecationWarning)
        tr = GLMTrainer(X, y, objective="logistic", lam=1e-2, cfg=DET)
    tr.fit(max_epochs=3, tol=0.0)
    np.testing.assert_array_equal(np.asarray(ses.v), np.asarray(tr.v))
    np.testing.assert_array_equal(np.asarray(ses.alpha),
                                  np.asarray(tr.alpha))


def test_session_pads_arbitrary_n():
    X, y = _dense(n=500)          # 500 does not divide the topology
    ses = Session((X, y), lam=1e-2, cfg=DET)
    assert ses.n_examples == 500 and ses.n % (2 * 2 * 2 * 8) == 0
    res = ses.fit(max_epochs=5, tol=1e-4)
    assert np.isfinite(res.final_gap)


def test_session_from_feed_matches_resident():
    from repro.data.cache import ArrayFeed
    X, y = _dense(n=256, d=16)
    resident = Session((X, y), lam=1e-2, cfg=DET)
    resident.fit(max_epochs=2, tol=0.0)
    feed = ArrayFeed(y, X=X, bucket=8)
    streamed = Session(feed, objective="logistic", lam=1e-2, cfg=DET)
    assert streamed.streamed
    streamed.fit(max_epochs=2, tol=0.0)
    np.testing.assert_array_equal(np.asarray(resident.v),
                                  np.asarray(streamed.v))
    np.testing.assert_array_equal(np.asarray(resident.alpha),
                                  np.asarray(streamed.alpha))
    # diagnostics flow through the feed's streaming pass
    assert streamed.gap() == pytest.approx(resident.gap(),
                                           rel=1e-4, abs=1e-6)


def test_session_streamed_arrays_match_resident():
    """streamed=True over plain arrays wraps an ArrayFeed: chunked
    device residency, bitwise-identical training, working gap()."""
    X, y = _dense(n=256, d=16)
    resident = Session((X, y), lam=1e-2, cfg=DET)
    resident.fit(max_epochs=2, tol=0.0)
    streamed = Session((X, y), lam=1e-2, cfg=DET, streamed=True)
    assert streamed.streamed and streamed.feed is not None
    streamed.fit(max_epochs=2, tol=0.0)
    np.testing.assert_array_equal(np.asarray(resident.v),
                                  np.asarray(streamed.v))
    assert streamed.gap() == pytest.approx(resident.gap(),
                                           rel=1e-4, abs=1e-6)


def test_session_registry_and_cache_sources(tmp_path):
    res = Session("synthetic-dense", n=256, d=32, cfg=DET).fit(
        max_epochs=3, tol=0.0)
    cache = registry.materialize("synthetic-dense", tmp_path, bucket=8,
                                 n=256, d=32, pad_multiple=64)
    ses = Session(cache, cfg=DET, streamed=True)
    res2 = ses.fit(max_epochs=3, tol=0.0)
    assert res2.epochs == 3
    assert np.abs(res2.v).max() > 0
    assert np.isfinite(res.final_gap) and np.isfinite(res2.final_gap)


# -- callbacks --------------------------------------------------------------

def test_callbacks_early_stop_logger_recorder():
    X, y = _dense()
    logger = GapLogger(every=1, printer=None)
    stop = EarlyStopping(monitor="gap", threshold=1e-3)
    ses = Session((X, y), lam=1e-2, cfg=DET)
    obs.reset()
    res = ses.fit(until=50, tol=0.0, callbacks=[logger, stop])
    assert res.epochs < 50                      # certificate stop fired
    assert logger.trace and logger.trace[-1][1] < 1e-3
    assert obs.counters()["epochs"] == res.epochs
    assert res.wall_time > 0


def test_bare_callable_callback_stops():
    X, y = _dense()
    ses = Session((X, y), lam=1e-2, cfg=DET)
    res = ses.fit(until=50, tol=0.0,
                  callbacks=[lambda m: m["epoch"] >= 2])
    assert res.epochs == 2


def test_checkpoint_hook_saves_steps(tmp_path):
    from repro.api import CheckpointHook
    X, y = _dense()
    hook = CheckpointHook(tmp_path / "ck", every=2, keep_n=2)
    ses = Session((X, y), lam=1e-2, cfg=DET)
    ses.fit(until=5, tol=0.0, callbacks=[hook])
    hook.mgr.wait()
    assert hook.mgr.all_steps() == [2, 4]


# -- estimators -------------------------------------------------------------

def test_estimator_sklearn_protocol():
    est = LogisticRegression(lam=1e-2, lanes=4, max_epochs=7)
    params = est.get_params()
    assert params["lanes"] == 4 and params["max_epochs"] == 7
    clone = LogisticRegression(**params)
    assert clone.get_params() == params
    est.set_params(lanes=2, tol=1e-5)
    assert est.lanes == 2 and est.tol == 1e-5
    with pytest.raises(ValueError, match="invalid parameter"):
        est.set_params(nope=1)
    with pytest.raises(NotFittedError):
        est.predict(np.zeros((3, 4)))


def test_logreg_fit_predict_score_proba():
    X, y = _dense(n=1024, d=32)
    Xsk = X.T                                    # sklearn layout
    y01 = (y > 0).astype(int)                    # arbitrary binary labels
    est = LogisticRegression(lam=1e-3, bucket=8, lanes=2, max_epochs=40,
                             tol=1e-4)
    assert est.fit(Xsk, y01) is est
    assert list(est.classes_) == [0, 1]
    preds = est.predict(Xsk)
    assert set(np.unique(preds)) <= {0, 1}
    assert est.score(Xsk, y01) > 0.6
    proba = est.predict_proba(Xsk)
    np.testing.assert_allclose(proba.sum(axis=1), 1.0, atol=1e-6)
    assert np.array_equal(preds, est.classes_[
        (est.decision_function(Xsk) > 0).astype(int)])
    assert est.coef_.shape == (32,) and est.n_iter_ > 0


def test_linear_svc_and_ridge():
    X, y = _dense(n=512, d=16)
    svc = LinearSVC(lam=1e-3, bucket=8, max_epochs=30)
    svc.fit(X.T, y)
    assert svc.score(X.T, y) > 0.6

    rng = np.random.default_rng(0)
    Xr = rng.standard_normal((400, 12)).astype(np.float32)
    w = rng.standard_normal(12).astype(np.float32)
    yr = Xr @ w + 0.01 * rng.standard_normal(400).astype(np.float32)
    ridge = Ridge(lam=1e-4, bucket=8, max_epochs=60, tol=1e-6)
    ridge.fit(Xr, yr)
    assert ridge.score(Xr, yr) > 0.98


def test_estimator_sparse_pair_input():
    (idx, val), y, d = make_sparse_classification(n=512, d=128, nnz=8,
                                                  seed=3)
    est = LogisticRegression(lam=1e-3, bucket=8, max_epochs=30,
                             n_features=d)
    est.fit((idx, val), y)
    acc = est.score((idx, val), y)
    assert acc > 0.6
    assert est.coef_.shape == (d,)


def test_estimator_streamed_from_cache(tmp_path):
    cache = registry.materialize("synthetic-dense", tmp_path, bucket=8,
                                 n=256, d=32, pad_multiple=64)
    est = LogisticRegression(bucket=8, max_epochs=5, streamed=True)
    est.fit(cache)
    assert est.session_.streamed
    assert est.n_iter_ > 0 and np.abs(est.coef_).max() > 0


# -- whole-estimator checkpointing ------------------------------------------

@pytest.mark.parametrize("kind", ["dense", "sparse"])
def test_estimator_checkpoint_resume_bitwise(tmp_path, kind):
    """fit(3) -> save -> load -> fit(remaining) == one straight fit,
    bitwise, dense and sparse, under deterministic=True."""
    common = dict(lam=1e-2, bucket=8, pods=1, lanes=2, chunks=2,
                  deterministic=True, tol=0.0)
    if kind == "dense":
        X, y = _dense(n=256, d=16)
        fit_args = (X.T, y)
        common["partition"] = "hierarchical"
    else:
        (idx, val), y, d = make_sparse_classification(n=256, d=64,
                                                      nnz=8, seed=1)
        fit_args = ((idx, val), y)
        common["n_features"] = d

    straight = LogisticRegression(max_epochs=8, **common)
    straight.fit(*fit_args)

    half = LogisticRegression(max_epochs=3, **common)
    half.fit(*fit_args)
    half.save(tmp_path / "est")

    resumed = load_estimator(tmp_path / "est")
    assert type(resumed) is LogisticRegression
    assert resumed.n_iter_ == 3
    # predicts immediately, without refitting
    np.testing.assert_array_equal(resumed.predict(fit_args[0]),
                                  half.predict(fit_args[0]))
    resumed.set_params(max_epochs=8)
    resumed.fit(*fit_args)
    assert resumed.n_iter_ == 8
    np.testing.assert_array_equal(resumed.coef_, straight.coef_)
    np.testing.assert_array_equal(np.asarray(resumed.session_.alpha),
                                  np.asarray(straight.session_.alpha))


def test_loaded_estimator_fit_without_budget_reports_state(tmp_path):
    """fit() on a loaded estimator whose budget is already spent runs 0
    epochs but still reports a REAL gap, not nan."""
    X, y = _dense(n=256, d=16)
    est = LogisticRegression(bucket=8, max_epochs=3, tol=0.0)
    est.fit(X.T, y)
    est.save(tmp_path / "est")
    again = load_estimator(tmp_path / "est")
    again.fit(X.T, y)
    assert again.n_iter_ == 3
    assert np.isfinite(again.fit_result_.final_gap)
    np.testing.assert_array_equal(again.coef_, est.coef_)


def test_resume_rejects_different_n(tmp_path):
    X, y = _dense(n=256, d=16)
    est = LogisticRegression(bucket=8, max_epochs=2, tol=0.0)
    est.fit(X.T, y)
    est.save(tmp_path / "est")
    X2, y2 = _dense(n=512, d=16, seed=1)
    resumed = load_estimator(tmp_path / "est")
    with pytest.raises(ValueError, match="checkpoint n="):
        resumed.fit(X2.T, y2)


def test_save_warns_on_unserializable_params(tmp_path):
    X, y = _dense(n=256, d=16)
    est = LogisticRegression(bucket=8, max_epochs=2, tol=0.0,
                             callbacks=[lambda m: None])
    est.fit(X.T, y)
    with pytest.warns(UserWarning, match="callbacks"):
        est.save(tmp_path / "est")
    assert load_estimator(tmp_path / "est").callbacks is None


def test_estimator_load_rejects_wrong_class(tmp_path):
    X, y = _dense(n=256, d=16)
    est = LogisticRegression(bucket=8, max_epochs=2, tol=0.0)
    est.fit(X.T, y)
    est.save(tmp_path / "est")
    with pytest.raises(ValueError, match="LogisticRegression"):
        Ridge.load(tmp_path / "est")


# -- sklearn parity (the acceptance criterion) ------------------------------

def test_sklearn_parity_on_registry_dataset():
    sklearn = pytest.importorskip("sklearn")  # noqa: F841
    from sklearn.linear_model import LogisticRegression as SkLR

    ds = registry.get_dataset("synthetic-dense")   # 2048 x 64
    Xsk, y = np.asarray(ds.X).T, np.asarray(ds.y)
    lam = 1e-3
    ours = LogisticRegression(lam=lam, bucket=8, lanes=4,
                              partition="dynamic", max_epochs=100,
                              tol=1e-5)
    ours.fit(Xsk, y)
    theirs = SkLR(C=1.0 / (lam * y.shape[0]), fit_intercept=False,
                  solver="lbfgs", max_iter=1000, tol=1e-8)
    theirs.fit(Xsk, y)

    assert abs(ours.score(Xsk, y) - theirs.score(Xsk, y)) <= 1e-2
    agree = np.mean(ours.predict(Xsk) == theirs.predict(Xsk))
    assert agree >= 0.99


def test_scipy_csr_input_matches_pair():
    sp = pytest.importorskip("scipy.sparse")
    (idx, val), y, d = make_sparse_classification(n=256, d=64, nnz=8,
                                                  seed=2)
    n, nnz = idx.shape
    rows = np.repeat(np.arange(n), nnz)
    mat = sp.csr_matrix((val.ravel(), (rows, idx.ravel())), shape=(n, d))
    kw = dict(lam=1e-2, bucket=8, max_epochs=5, tol=0.0,
              deterministic=True, n_features=d)
    a = LogisticRegression(**kw).fit(mat, y)
    b = LogisticRegression(**kw).fit((idx, val), y)
    # scipy sums duplicate (row, col) entries and reorders columns, so
    # the padded rows agree only up to f32 summation order
    np.testing.assert_allclose(a.coef_, b.coef_, rtol=1e-2, atol=1e-4)
    np.testing.assert_array_equal(a.predict(mat), b.predict((idx, val)))


# -- serving ----------------------------------------------------------------

def test_serve_glm_batch_and_streamed(tmp_path):
    from repro.launch.serve import glm_predict_batch, glm_predict_streamed

    cache = registry.materialize("synthetic-dense", tmp_path, bucket=8,
                                 n=256, d=32, pad_multiple=64)
    est = LogisticRegression(bucket=8, max_epochs=10)
    est.fit(cache)
    X, _y = cache.load_arrays()
    Xsk = np.asarray(X).T[:cache.meta.n_examples]

    direct = est.predict(Xsk)
    batched = glm_predict_batch(est, Xsk, batch=50)
    np.testing.assert_array_equal(direct, batched)
    proba = glm_predict_batch(est, Xsk, batch=50, proba=True)
    assert proba.shape == (Xsk.shape[0], 2)

    streamed = glm_predict_streamed(est, cache, gbuckets=4)
    np.testing.assert_array_equal(direct, streamed)


def test_estimator_epoch_lowers_to_mesh():
    from repro.launch.glm import estimator_epoch, glm_input_specs
    from repro.launch.mesh import make_host_mesh
    import jax

    X, y = _dense(n=256, d=16)
    est = LogisticRegression(lam=1e-2, bucket=8, max_epochs=2, tol=0.0)
    est.fit(X.T, y)
    mesh = make_host_mesh(pod=1, data=1, model=1)
    epoch_fn, scale = estimator_epoch(est, mesh)
    assert scale.kind == "dense" and scale.n == est.session_.n
    assert scale.bucket == 8 and scale.lam == pytest.approx(1e-2)
    specs = glm_input_specs(scale, mesh)
    assert specs[0].shape == (scale.d, scale.n)
    ses = est.session_
    with mesh:
        Xm, ym, am, vm = jax.jit(epoch_fn)(
            ses.X, ses.y, jnp.zeros(ses.n), jnp.zeros(ses.d),
            jnp.int32(0))
    assert vm.shape == (scale.d,)
    assert np.isfinite(np.asarray(vm)).all()
    assert np.abs(np.asarray(vm)).max() > 0


def test_estimator_epoch_requires_fitted():
    from repro.launch.glm import scale_for_estimator
    with pytest.raises(ValueError, match="fitted"):
        scale_for_estimator(LogisticRegression())


# -- deprecation shims ------------------------------------------------------

def test_legacy_entry_points_warn_once():
    from repro.core import (GLMTrainer, StreamedGLMTrainer, cocoa,
                            fit_dataset)
    from repro.core.bucketing import make_plan
    from repro.core.objectives import LOGISTIC
    from repro.core.partition import PartitionPlan

    X, y = _dense(n=128, d=8)
    reset_deprecation_registry()

    with pytest.warns(ReproDeprecationWarning, match="GLMTrainer"):
        tr = GLMTrainer(X, y, cfg=SolverConfig(bucket=8))
    # once per process: a second construction is silent
    with warnings.catch_warnings():
        warnings.simplefilter("error", ReproDeprecationWarning)
        GLMTrainer(X, y, cfg=SolverConfig(bucket=8))

    with pytest.warns(ReproDeprecationWarning, match="fit_dataset"):
        fit_dataset("synthetic-dense", n=128, d=16, max_epochs=1,
                    tol=0.0)

    plan = PartitionPlan(n_buckets=16, pods=1, lanes=2)
    bplan = make_plan(128, 8, force=8)
    with pytest.warns(ReproDeprecationWarning, match="epoch_sim"):
        cocoa.epoch_sim(LOGISTIC, jnp.asarray(X), jnp.asarray(y),
                        tr.alpha * 0, tr.v * 0, 1e-3, plan, bplan,
                        SolverConfig(lanes=2, bucket=8), jnp.int32(0))

    (idx, val), ys, d = make_sparse_classification(n=128, d=32, nnz=4,
                                                   seed=0)
    with pytest.warns(ReproDeprecationWarning, match="epoch_sim_sparse"):
        cocoa.epoch_sim_sparse(
            LOGISTIC, jnp.asarray(idx), jnp.asarray(val),
            jnp.asarray(ys), jnp.zeros(128), jnp.zeros(d), 1e-3,
            PartitionPlan(n_buckets=16, pods=1, lanes=2),
            make_plan(128, d, force=8),
            SolverConfig(lanes=2, bucket=8), jnp.int32(0))


def test_streamed_trainer_shim_warns(tmp_path):
    from repro.core import StreamedGLMTrainer
    cache = registry.materialize("synthetic-dense", tmp_path, bucket=8,
                                 n=256, d=32, pad_multiple=64)
    reset_deprecation_registry()
    with pytest.warns(ReproDeprecationWarning, match="StreamedGLMTrainer"):
        tr = StreamedGLMTrainer(cache, cfg=SolverConfig(bucket=8))
    assert tr.plan.n_buckets == tr.n // 8


# -- local-solver dispatch (satellite) --------------------------------------

def test_sparse_local_solver_auto_resolves_to_xla():
    # off-TPU (every CI host), "auto" still means the XLA scan; the
    # TPU->pallas resolution is pinned in test_engine.py
    from repro.core import make_local_solver
    from repro.core.objectives import LOGISTIC

    solver = make_local_solver("auto", LOGISTIC, 1.0, 1.0, sparse=True)
    assert callable(solver)
    # behaves identically to an explicit "xla"
    (idx, val), y, d = make_sparse_classification(n=8, d=16, nnz=4,
                                                  seed=0)
    xla = make_local_solver("xla", LOGISTIC, 1.0, 1.0, sparse=True)
    args = ((jnp.asarray(idx), jnp.asarray(val)), jnp.asarray(y),
            jnp.zeros(8), jnp.zeros(d))
    a1, dv1 = solver(*args)
    a2, dv2 = xla(*args)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_array_equal(np.asarray(dv1), np.asarray(dv2))
    with pytest.raises(ValueError, match="unknown local_solver"):
        make_local_solver("nope", LOGISTIC, 1.0, 1.0, sparse=True)


def test_session_rejects_duplicate_nonzeros_for_pallas(monkeypatch):
    """Ad-hoc sparse rows that repeat a feature id with NONZERO values
    are rejected at Session entry when the resolved solver is the
    Pallas kernel (arrays are still concrete there; inside the jitted
    epoch they're tracers) — and stay accepted on the XLA scan, which
    accumulates duplicates fine."""
    from repro.api import Session
    from repro.core.config import EngineConfig

    (idx, val), y, d = make_sparse_classification(n=64, d=32, nnz=8,
                                                  seed=5)
    bad_idx = np.asarray(idx).copy()
    bad_val = np.asarray(val).copy()
    bad_idx[2, 1] = bad_idx[2, 0]
    bad_val[2, :2] = [0.5, 0.25]
    cfg = EngineConfig.make(pods=1, lanes=2, bucket=8,
                            local_solver="pallas")
    with pytest.raises(ValueError, match="zero_duplicates"):
        Session(((bad_idx, bad_val), y), objective="logistic", lam=1e-2,
                d=d, cfg=cfg)
    # CPU "auto" resolves to xla -> duplicates remain acceptable
    cfg_auto = EngineConfig.make(pods=1, lanes=2, bucket=8,
                                 local_solver="auto")
    Session(((bad_idx, bad_val), y), objective="logistic", lam=1e-2,
            d=d, cfg=cfg_auto).fit(max_epochs=1)
    # TPU "auto": enforced when the kernel would run the rows...
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="zero_duplicates"):
        Session(((bad_idx, bad_val), y), objective="logistic",
                lam=1e-2, d=d, cfg=cfg_auto)
    # ...but NOT when the engine's misfit fallback routes the workload
    # to the XLA scan anyway (nnz=7 breaks the sublane alignment)
    Session(((bad_idx[:, :7], bad_val[:, :7]), y), objective="logistic",
            lam=1e-2, d=d, cfg=cfg_auto)
    # the misfit pre-check must see the RESOLVED bucket: cfg leaves
    # bucket at the default 1 (which could never fit the kernel) and
    # the Session kwarg supplies the real, kernel-fitting bucket
    cfg_nobucket = EngineConfig.make(pods=1, lanes=2,
                                     local_solver="auto")
    with pytest.raises(ValueError, match="zero_duplicates"):
        Session(((bad_idx, bad_val), y), objective="logistic",
                lam=1e-2, d=d, bucket=8, cfg=cfg_nobucket)
    # a user-supplied ArrayFeed is checked at Session entry too (the
    # jitted streamed step only ever sees tracers)
    from repro.data.cache import ArrayFeed
    feed = ArrayFeed(y, idx=bad_idx, val=bad_val, d=d, bucket=8)
    with pytest.raises(ValueError, match="zero_duplicates"):
        Session(feed, objective="logistic", lam=1e-2, cfg=cfg_auto)


# -- bench compare (CI perf-trajectory satellite) ---------------------------

def test_bench_compare_flags_regressions():
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from benchmarks.compare import compare

    prev = {"schema": "bench-summary/v1", "quick": True,
            "figures": {"fig1": {"failed": False, "runtime_s": 10.0,
                                 "final_gap": 1e-4},
                        "fig2": {"failed": False, "runtime_s": 5.0,
                                 "final_gap": None}}}
    ok = {"schema": "bench-summary/v1", "quick": True,
          "figures": {"fig1": {"failed": False, "runtime_s": 11.0,
                               "final_gap": 1.1e-4},
                      "fig2": {"failed": False, "runtime_s": 5.5,
                               "final_gap": None}}}
    assert compare(prev, ok) == []

    slow = {"schema": "bench-summary/v1", "quick": True,
            "figures": {"fig1": {"failed": False, "runtime_s": 14.0,
                                 "final_gap": 1e-4},
                        "fig2": {"failed": True, "runtime_s": 1.0}}}
    problems = compare(prev, slow)
    assert any("runtime" in p for p in problems)
    assert any("FAILING" in p for p in problems)

    worse_gap = {"schema": "bench-summary/v1", "quick": True,
                 "figures": {"fig1": {"failed": False, "runtime_s": 10.0,
                                      "final_gap": 2e-4},
                             "fig2": {"failed": False, "runtime_s": 5.0,
                                      "final_gap": None}}}
    assert any("gap" in p for p in compare(prev, worse_gap))
    # quick vs full runs are never compared
    assert compare(prev, dict(worse_gap, quick=False)) == []
    # a workload-version bump resets the baseline on purpose
    assert compare(prev, dict(worse_gap, workload=3)) == []
    # a vanished figure is a regression
    assert any("disappeared" in p
               for p in compare(prev, {"schema": "bench-summary/v1",
                                       "quick": True, "figures": {}}))


def test_bench_compare_parity_trajectory():
    """The sklearn-parity gate (PR-4 satellite): an absolute
    predict_agree floor on every run + vanished parity records count
    as regressions."""
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from benchmarks.compare import compare, parity_floor_problems

    rec = {"dataset": "higgs", "solver": "estimator",
           "score": 0.9, "score_sklearn": 0.9, "predict_agree": 0.999}
    good = {"schema": "bench-summary/v1", "quick": True,
            "figures": {"fig6": {"failed": False, "runtime_s": 5.0,
                                 "parity": [rec]}}}
    assert parity_floor_problems(good) == []

    bad = {"schema": "bench-summary/v1", "quick": True,
           "figures": {"fig6": {"failed": False, "runtime_s": 5.0,
                                "parity": [dict(rec,
                                                predict_agree=0.97)]}}}
    probs = parity_floor_problems(bad)
    assert probs and "0.99" in probs[0] and "fig6" in probs[0]
    # a custom floor is honoured
    assert parity_floor_problems(bad, floor=0.9) == []
    # an already-failed figure doesn't double-report
    failed = {"figures": {"fig6": {"failed": True,
                                   "parity": [dict(rec,
                                                   predict_agree=0.5)]}}}
    assert parity_floor_problems(failed) == []

    # cross-run: losing a parity record is a regression, keeping it is
    # fine even if the value moved (the absolute floor owns the value)
    lost = {"schema": "bench-summary/v1", "quick": True,
            "figures": {"fig6": {"failed": False, "runtime_s": 5.0}}}
    assert any("parity" in p and "disappeared" in p
               for p in compare(good, lost))
    moved = {"schema": "bench-summary/v1", "quick": True,
             "figures": {"fig6": {"failed": False, "runtime_s": 5.0,
                                  "parity": [dict(rec,
                                                  predict_agree=0.992)]}}}
    assert compare(good, moved) == []
