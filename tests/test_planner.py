"""core.planner: decision table, plan cache, never-regress pins.

Three layers, mirroring DESIGN.md S13's contract:

  * decision-table tests drive (d, B, nnz, M, backend) corners —
    including EXACT VMEM boundaries computed from the kernels' own
    budget constants — through `resolve_plan` and assert the route;
  * plan-cache tests pin the round-trip, the version-bump
    invalidation, and that $REPRO_PLAN=off never touches disk;
  * never-regress pins: planner-resolved auto must equal
    static-resolved auto BITWISE on every previously-working config —
    at the plan level, at the Session level (same epoch output), and
    at the scale_for_dataset level (same GLMScale).
"""
import dataclasses
import json

import numpy as np
import pytest

from repro.core import planner
from repro.core.planner import (PLAN_VERSION, SolverPlan, Topology,
                                WorkloadSignature)
from repro.kernels import ops as kops
from repro.kernels.sdca_sparse_bucket import (TOTAL_VMEM_BUDGET_BYTES,
                                              V_VMEM_BUDGET_BYTES)

TPU1 = Topology(backend="tpu")
TPU_M2 = Topology(backend="tpu", device_count=2, model_lanes=2)

# exact resident-v boundary: largest d whose padded f32 shared vector
# fits the sparse kernel's VMEM budget, and the first d past it
D_V_FIT = V_VMEM_BUDGET_BYTES // 4
D_V_OVER = D_V_FIT + 8
assert D_V_FIT % 8 == 0


def _plan(sig, topo, **kw):
    kw.setdefault("use_cache", False)
    return planner.resolve_plan(sig, topo, **kw)


def sparse_sig(d, nnz, n=4096, name=""):
    return WorkloadSignature(n=n, d=d, nnz=nnz, sparse=True, name=name)


# -- decision table ---------------------------------------------------------


@pytest.mark.parametrize(
    "d,bucket,nnz,topo,route",
    [
        # aligned, small: replicated kernel
        (1024, 8, 40, TPU1, "pallas-replicated"),
        (1024, 16, 40, TPU_M2, "pallas-replicated"),
        # alignment misfits -> xla (B and nnz must be sublane multiples)
        (1024, 12, 40, TPU1, "xla"),
        (1024, 8, 39, TPU1, "xla"),
        # exact resident-v boundary: d_pad*4 == budget still fits;
        # one sublane past it needs the sharded kernel (M > 1) or xla
        (D_V_FIT, 8, 8, TPU1, "pallas-replicated"),
        (D_V_OVER, 8, 8, TPU1, "xla"),
        (D_V_OVER, 8, 8, TPU_M2, "pallas-sharded"),
        # webspam over two model lanes: each 8.3M-feature slice is
        # over the resident-v budget
        (16_609_280, 16, 3728, TPU_M2, "xla"),
    ])
def test_sparse_decision_table(d, bucket, nnz, topo, route, monkeypatch):
    monkeypatch.delenv("REPRO_PLAN", raising=False)
    plan = _plan(sparse_sig(d, nnz), topo, bucket=bucket, chunks=1)
    assert plan.route == route
    # the planner's verdict is byte-identical to the kernels' own
    # dispatcher — it can never loosen feasibility
    want, why = kops.sparse_solver_plan(bucket, nnz, d, bucket,
                                        model_lanes=topo.model_lanes)
    assert plan.route == want
    if route == "xla":
        assert plan.reason == why


def test_total_budget_boundary():
    """Walk nnz across the total-footprint budget at fixed (B, d): the
    planner flips replicated -> xla exactly where the kernel's own
    estimate crosses TOTAL_VMEM_BUDGET_BYTES."""
    from repro.kernels.sdca_sparse_bucket import vmem_bytes_estimate
    # the footprint grows with B*nnz tiles: walk it at a wide bucket
    d, B = 1024, 512
    flipped = None
    for nnz in range(8, 4096, 8):
        fits = (vmem_bytes_estimate(B, nnz, d)
                <= TOTAL_VMEM_BUDGET_BYTES)
        plan = _plan(sparse_sig(d, nnz), TPU1, bucket=B, chunks=1)
        assert (plan.route == "pallas-replicated") == fits
        if not fits:
            flipped = nnz
            break
    assert flipped is not None, "never crossed the budget — widen range"


@pytest.mark.parametrize("bucket,route", [
    (8, "pallas-replicated"),
    (512, "pallas-replicated"),       # the dense kernel's bucket cap
    (520, "xla"),                     # one sublane past the cap
])
def test_dense_decision_table(bucket, route, monkeypatch):
    monkeypatch.delenv("REPRO_PLAN", raising=False)
    sig = WorkloadSignature(n=8 * bucket, d=64)
    plan = _plan(sig, TPU1, bucket=bucket, chunks=1)
    assert plan.route == route


def test_backend_picks_solver(monkeypatch):
    """Off-TPU the solver is xla even when the route says the kernel
    would fit (mirrors engine.resolve_auto_solver)."""
    monkeypatch.delenv("REPRO_PLAN", raising=False)
    sig = sparse_sig(1024, 40)
    assert _plan(sig, TPU1, bucket=8, chunks=1).solver == "pallas"
    cpu = Topology(backend="cpu")
    plan = _plan(sig, cpu, bucket=8, chunks=1)
    assert plan.solver == "xla" and plan.route == "pallas-replicated"


def test_feature_shard_default_matches_static_rule():
    # sparse: the replicated resident-v budget boundary
    assert not planner.feature_shard_default(sparse_sig(D_V_FIT, 8))
    assert planner.feature_shard_default(sparse_sig(D_V_OVER, 8))
    # dense: the TP width boundary
    assert not planner.feature_shard_default(WorkloadSignature(n=1, d=511))
    assert planner.feature_shard_default(WorkloadSignature(n=1, d=512))


def test_plan_mode_parsing(monkeypatch):
    monkeypatch.delenv("REPRO_PLAN", raising=False)
    assert planner.plan_mode() == "on"
    for m in ("off", "on", "search", "probe"):
        monkeypatch.setenv("REPRO_PLAN", m)
        assert planner.plan_mode() == m
    monkeypatch.setenv("REPRO_PLAN", "bogus")
    with pytest.raises(ValueError, match="REPRO_PLAN"):
        planner.plan_mode()


def test_search_respects_fixed_knobs(monkeypatch):
    monkeypatch.setenv("REPRO_PLAN", "search")
    sig = sparse_sig(1024, 40, n=4096)
    plan = _plan(sig, TPU1, bucket=16, chunks=4)
    assert (plan.bucket, plan.chunks) == (16, 4)
    free = _plan(sig, TPU1)
    assert free.bucket in planner.BUCKET_CANDIDATES
    assert free.route != "xla"        # search found a kernel geometry


def test_search_never_loosens_feasibility(monkeypatch):
    """Every candidate the search can emit passes the kernels' misfit
    predicates (or routes xla) — spot-check the whole candidate set."""
    monkeypatch.setenv("REPRO_PLAN", "search")
    # no kernel fits: even the d/2 slice is over the resident-v budget
    sig = sparse_sig(4 * D_V_FIT, 3728, n=8192)
    for cand in planner.candidate_plans(sig, TPU_M2):
        if cand.solver == "pallas":
            assert kops.sparse_kernel_misfit(
                cand.bucket, sig.nnz, sig.d, cand.bucket,
                model_lanes=TPU_M2.model_lanes if cand.feature_shard
                else 1) is None
    plan = _plan(sig, TPU_M2)
    assert plan.route == "xla"
    # and the layout never drifts from the static rule on a tie
    assert plan.feature_shard == planner.feature_shard_default(sig,
                                                               TPU_M2)


def test_probe_refinement(monkeypatch):
    """Probe mode times the ranked candidates and returns the fastest;
    a raising probe disqualifies its candidate only."""
    monkeypatch.setenv("REPRO_PLAN", "probe")
    sig = sparse_sig(1024, 40, n=4096)
    seen = []

    def probe(plan):
        seen.append((plan.bucket, plan.chunks))
        if len(seen) == 1:
            raise RuntimeError("first candidate crashes")
        return 0.5 / plan.bucket        # bigger bucket "measures" faster

    with pytest.warns(UserWarning, match="probe failed"):
        plan = _plan(sig, TPU1, probe_fn=probe)
    assert plan.origin == "probe" and plan.probe_s > 0
    assert (plan.bucket, plan.chunks) == max(seen[1:])[:2] or \
        plan.bucket == max(b for b, _ in seen[1:])


# -- plan cache -------------------------------------------------------------


def test_plan_cache_roundtrip(tmp_path):
    sig = sparse_sig(1024, 40, name="unit")
    plan = planner.static_plan(sig, TPU1, bucket=8, chunks=2)
    path = planner.store_plan(sig, TPU1, plan, cache_dir=tmp_path)
    assert path.parent == tmp_path / "plans"
    got = planner.load_cached_plan(sig, TPU1, cache_dir=tmp_path)
    assert got is not None and got.origin == "cache"
    assert dataclasses.replace(got, origin=plan.origin) == plan
    # a different topology or workload misses
    assert planner.load_cached_plan(sig, TPU_M2,
                                    cache_dir=tmp_path) is None
    assert planner.load_cached_plan(sparse_sig(2048, 40, name="unit"),
                                    TPU1, cache_dir=tmp_path) is None


def test_plan_cache_version_bump_invalidates(tmp_path, monkeypatch):
    sig = sparse_sig(1024, 40, name="unit")
    plan = planner.static_plan(sig, TPU1, bucket=8, chunks=2)
    path = planner.store_plan(sig, TPU1, plan, cache_dir=tmp_path)
    monkeypatch.setattr(planner, "PLAN_VERSION", PLAN_VERSION + 1)
    assert planner.load_cached_plan(sig, TPU1, cache_dir=tmp_path) is None
    # even a hand-renamed file is rejected by the stored version field
    monkeypatch.undo()
    doc = json.loads(path.read_text())
    doc["version"] = PLAN_VERSION + 1
    path.write_text(json.dumps(doc))
    assert planner.load_cached_plan(sig, TPU1, cache_dir=tmp_path) is None
    # corruption degrades to a miss, never an exception
    path.write_text("{not json")
    assert planner.load_cached_plan(sig, TPU1, cache_dir=tmp_path) is None


def test_search_caches_and_rehits(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_PLAN", "search")
    sig = sparse_sig(1024, 40, n=4096, name="unit")
    first = planner.resolve_plan(sig, TPU1, cache_dir=tmp_path)
    assert first.origin == "search"
    again = planner.resolve_plan(sig, TPU1, cache_dir=tmp_path)
    assert again.origin == "cache"
    assert dataclasses.replace(again, origin="x") == \
        dataclasses.replace(first, origin="x")


def test_plan_off_never_touches_disk(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_PLAN", "off")
    sig = sparse_sig(1024, 40, name="unit")
    planner.resolve_plan(sig, TPU1, cache_dir=tmp_path / "nope")
    assert not (tmp_path / "nope").exists()


def test_cached_plan_rechecks_feasibility(tmp_path):
    """A cached pallas plan that no longer passes the misfit predicates
    (e.g. budgets tightened between releases) is ignored."""
    sig = sparse_sig(1024, 40, name="unit")
    good = planner.static_plan(sig, TPU1, bucket=8, chunks=1)
    assert good.route == "pallas-replicated"
    bad = dataclasses.replace(good, bucket=12)     # now misaligned
    planner.store_plan(sig, TPU1, bad, cache_dir=tmp_path)
    assert planner.load_cached_plan(sig, TPU1, cache_dir=tmp_path) is None


# -- never-regress pins -----------------------------------------------------

WORKING_CONFIGS = [
    # (sig, topo, bucket, chunks) — every previously-working shape class
    (sparse_sig(1024, 40, n=4096), TPU1, 8, 2),          # criteo-ish
    (sparse_sig(1024, 40, n=4096), Topology(backend="cpu"), 8, 2),
    (sparse_sig(D_V_OVER, 64, n=128), TPU_M2, 8, 2),     # webspam-ish
    (sparse_sig(1024, 39, n=4096), TPU1, 8, 2),          # unaligned nnz
    (WorkloadSignature(n=4096, d=28), TPU1, 8, 4),       # higgs-ish
    (WorkloadSignature(n=4096, d=2000), TPU_M2, 16, 8),  # epsilon-ish
    (WorkloadSignature(n=4096, d=64), TPU1, 1, 1),       # bucketing off
]


@pytest.mark.parametrize("sig,topo,bucket,chunks", WORKING_CONFIGS)
def test_planner_auto_equals_static_auto(sig, topo, bucket, chunks,
                                         monkeypatch):
    """THE PR-4 contract: under the default $REPRO_PLAN the planner's
    resolution is bitwise the static resolution on every
    previously-working config."""
    monkeypatch.setenv("REPRO_PLAN", "off")
    off = _plan(sig, topo, bucket=bucket, chunks=chunks)
    monkeypatch.delenv("REPRO_PLAN")
    on = _plan(sig, topo, bucket=bucket, chunks=chunks)
    assert (on.solver, on.route, on.bucket, on.chunks, on.nnz_multiple,
            on.feature_shard) == \
           (off.solver, off.route, off.bucket, off.chunks,
            off.nnz_multiple, off.feature_shard)


def test_route_functions_equal_kernel_predicates():
    """The engine's misfit closures route through planner.route_* —
    pin them to the kernels' own predicates verbatim."""
    for (sig, topo, bucket, _) in WORKING_CONFIGS:
        if sig.sparse:
            assert planner.route_sparse(
                bucket, sig.nnz, sig.d, bucket,
                model_lanes=topo.model_lanes) == kops.sparse_solver_plan(
                bucket, sig.nnz, sig.d, bucket,
                model_lanes=topo.model_lanes)
        else:
            assert planner.route_dense(sig.d, bucket, bucket) == \
                kops.dense_kernel_misfit(sig.d, bucket, bucket)


def test_session_bitwise_pin(monkeypatch, tmp_path):
    """Session(auto) trains bitwise-identically with the planner on vs
    off, and records the resolved plan when on."""
    from repro.api.session import Session
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    rng = np.random.default_rng(0)
    X = rng.normal(size=(16, 256)).astype(np.float32)
    y = np.sign(rng.normal(size=256)).astype(np.float32)

    def fit(mode):
        if mode is None:
            monkeypatch.delenv("REPRO_PLAN", raising=False)
        else:
            monkeypatch.setenv("REPRO_PLAN", mode)
        ses = Session(X, y, objective="logistic", lam=1e-3)
        ses.fit(max_epochs=3, tol=0.0)
        return ses

    on, off = fit(None), fit("off")
    assert on.solver_plan is not None and off.solver_plan is None
    assert on.bplan.bucket == off.bplan.bucket
    assert on.spec.algo.chunks == off.spec.algo.chunks
    np.testing.assert_array_equal(np.asarray(on.v), np.asarray(off.v))
    np.testing.assert_array_equal(np.asarray(on.alpha),
                                  np.asarray(off.alpha))


def test_session_search_mode_sets_geometry(monkeypatch, tmp_path):
    from repro.api.session import Session
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("REPRO_PLAN", "search")
    rng = np.random.default_rng(1)
    X = rng.normal(size=(16, 4096)).astype(np.float32)
    y = np.sign(rng.normal(size=4096)).astype(np.float32)
    ses = Session(X, y, objective="logistic", lam=1e-3)
    assert ses.solver_plan is not None
    assert ses.bplan.bucket == ses.solver_plan.bucket > 1
    assert ses.spec.algo.chunks == ses.solver_plan.chunks
    ses.epoch()                                  # the geometry trains
    # an explicit bucket kwarg still wins over the search
    pinned = Session(X, y, objective="logistic", lam=1e-3, bucket=8)
    assert pinned.bplan.bucket == 8


def test_scale_for_dataset_pin(monkeypatch, tmp_path):
    """scale_for_dataset resolves its layout through the planner and is
    byte-identical to the retired hardcoded rule on every registry
    dataset."""
    from repro.launch.glm import scale_for_dataset

    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    names = ["criteo-kaggle-sub", "higgs", "epsilon", "webspam",
             "synthetic-dense", "synthetic-sparse"]
    monkeypatch.setenv("REPRO_PLAN", "off")
    off = [scale_for_dataset(n) for n in names]
    monkeypatch.delenv("REPRO_PLAN")
    on = [scale_for_dataset(n) for n in names]
    assert on == off
    # webspam keeps its sharded layout even under a full search
    monkeypatch.setenv("REPRO_PLAN", "search")
    assert scale_for_dataset("webspam").feature_shard
    # overrides always win
    assert scale_for_dataset("webspam", bucket=32, chunks=2,
                             feature_shard=False).bucket == 32


def test_resolve_plan_degrades_warn_and_safe(monkeypatch):
    """Any planner internals failure falls back to the static plan with
    a warning — never an exception out of resolve_plan."""
    monkeypatch.delenv("REPRO_PLAN", raising=False)
    monkeypatch.setattr(planner, "load_cached_plan",
                        lambda *a, **k: (_ for _ in ()).throw(
                            RuntimeError("cache exploded")))
    sig = sparse_sig(1024, 40)
    with pytest.warns(UserWarning, match="falling"):
        plan = planner.resolve_plan(sig, TPU1, bucket=8, chunks=2)
    assert plan.origin == "static"
    assert (plan.bucket, plan.chunks) == (8, 2)


def test_ops_plan_solver_entry(monkeypatch, tmp_path):
    """kernels.ops.plan_solver is the kernels-side door: detects the
    live topology and returns a plan honoring $REPRO_PLAN."""
    monkeypatch.delenv("REPRO_PLAN", raising=False)
    plan = kops.plan_solver(4096, 1024, nnz=40, sparse=True,
                            bucket=8, chunks=2, cache_dir=tmp_path)
    assert isinstance(plan, SolverPlan)
    assert (plan.bucket, plan.chunks) == (8, 2)
    import jax
    assert plan.solver == ("pallas" if jax.default_backend() == "tpu"
                           else "xla")
