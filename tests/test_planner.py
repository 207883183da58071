"""core.planner: the one function that chooses the local solver's route.

Three layers, mirroring DESIGN.md S13:

  * decision-table tests drive (d, B, nnz, M, backend) corners —
    including EXACT VMEM boundaries computed from the kernels' own
    budget constants — through `resolve_plan` and assert the route;
  * pins: every previously-working shape class, each benchmark cell's
    shape and every registry dataset's layout resolve to the literal
    plan they resolved to before the search, probe and plan cache were
    removed; Session "auto" trains bitwise like an explicit "xla" off
    the TPU;
  * `streamed_transfer_bytes` returns the same bytes on the shapes the
    fig4 benchmark and `glm_analytic` ask about.
"""
import numpy as np
import pytest

from repro.core import planner
from repro.kernels import ops as kops
from repro.kernels.sdca_sparse_bucket import (TOTAL_VMEM_BUDGET_BYTES,
                                              V_VMEM_BUDGET_BYTES)

# exact resident-v boundary: largest d whose padded f32 shared vector
# fits the sparse kernel's VMEM budget, and the first d past it
D_V_FIT = V_VMEM_BUDGET_BYTES // 4
D_V_OVER = D_V_FIT + 8
assert D_V_FIT % 8 == 0


def sparse_plan(d, nnz, n=4096, **kw):
    kw.setdefault("backend", "tpu")
    return planner.resolve_plan(sparse=True, n=n, d=d, nnz=nnz, **kw)


# -- decision table ---------------------------------------------------------


@pytest.mark.parametrize(
    "d,bucket,nnz,lanes,route",
    [
        # aligned, small: replicated kernel
        (1024, 8, 40, 1, "pallas-replicated"),
        (1024, 16, 40, 2, "pallas-replicated"),
        # alignment misfits -> xla (B and nnz must be sublane multiples)
        (1024, 12, 40, 1, "xla"),
        (1024, 8, 39, 1, "xla"),
        # exact resident-v boundary: d_pad*4 == budget still fits;
        # one sublane past it needs the sharded kernel (M > 1) or xla
        (D_V_FIT, 8, 8, 1, "pallas-replicated"),
        (D_V_OVER, 8, 8, 1, "xla"),
        (D_V_OVER, 8, 8, 2, "pallas-sharded"),
        # webspam over two model lanes: each 8.3M-feature slice is
        # over the resident-v budget
        (16_609_280, 16, 3728, 2, "xla"),
    ])
def test_sparse_decision_table(d, bucket, nnz, lanes, route):
    plan = sparse_plan(d, nnz, bucket=bucket, chunks=1, model_lanes=lanes)
    assert plan.route == route
    # the verdict is byte-identical to the kernels' own dispatcher —
    # the plan can never loosen feasibility
    want, why = kops.sparse_solver_plan(bucket, nnz, d, bucket,
                                        model_lanes=lanes)
    assert plan.route == want
    if route == "xla":
        assert plan.reason == why


def test_total_budget_boundary():
    """Walk nnz across the total-footprint budget at fixed (B, d): the
    plan flips replicated -> xla exactly where the kernel's own
    estimate crosses TOTAL_VMEM_BUDGET_BYTES."""
    from repro.kernels.sdca_sparse_bucket import vmem_bytes_estimate
    # the footprint grows with B*nnz tiles: walk it at a wide bucket
    d, B = 1024, 512
    flipped = None
    for nnz in range(8, 4096, 8):
        fits = (vmem_bytes_estimate(B, nnz, d)
                <= TOTAL_VMEM_BUDGET_BYTES)
        plan = sparse_plan(d, nnz, bucket=B, chunks=1)
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
def test_dense_decision_table(bucket, route):
    plan = planner.resolve_plan(sparse=False, n=8 * bucket, d=64,
                                bucket=bucket, chunks=1, backend="tpu")
    assert plan.route == route


def test_backend_picks_solver():
    """Off-TPU the solver is xla even when the route says the kernel
    would fit (what `local_solver="auto"` runs there)."""
    assert sparse_plan(1024, 40, bucket=8, chunks=1).solver == "pallas"
    plan = sparse_plan(1024, 40, bucket=8, chunks=1, backend="cpu")
    assert plan.solver == "xla" and plan.route == "pallas-replicated"


@pytest.mark.parametrize("backend,solver", [
    ("tpu", "pallas"), ("cpu", "xla"), ("gpu", "xla")])
def test_auto_solver_by_backend(backend, solver):
    """"auto" means pallas on a TPU backend and xla elsewhere; no
    environment variable changes that."""
    assert planner.auto_solver(backend) == solver


def test_feature_shard_default_matches_static_rule():
    # sparse: the replicated resident-v budget boundary
    assert not planner.feature_shard_default(True, D_V_FIT)
    assert planner.feature_shard_default(True, D_V_OVER)
    # dense: the TP width boundary
    assert not planner.feature_shard_default(False, 511)
    assert planner.feature_shard_default(False, 512)


# -- pins -------------------------------------------------------------------

WORKING_CONFIGS = [
    # (shape, model lanes, backend, bucket, chunks) and the literal
    # (solver, route, bucket, chunks, nnz_multiple, feature_shard)
    # each resolves to — every previously-working shape class
    (dict(sparse=True, n=4096, d=1024, nnz=40), 1, "tpu", 8, 2,
     ("pallas", "pallas-replicated", 8, 2, 0, False)),      # criteo-ish
    (dict(sparse=True, n=4096, d=1024, nnz=40), 1, "cpu", 8, 2,
     ("xla", "pallas-replicated", 8, 2, 0, False)),
    (dict(sparse=True, n=128, d=D_V_OVER, nnz=64), 2, "tpu", 8, 2,
     ("pallas", "pallas-sharded", 8, 2, 0, True)),          # webspam-ish
    (dict(sparse=True, n=4096, d=1024, nnz=39), 1, "tpu", 8, 2,
     ("xla", "xla", 8, 2, 0, False)),                       # unaligned nnz
    (dict(sparse=False, n=4096, d=28), 1, "tpu", 8, 4,
     ("pallas", "pallas-replicated", 8, 4, 0, False)),      # higgs-ish
    (dict(sparse=False, n=4096, d=2000), 2, "tpu", 16, 8,
     ("pallas", "pallas-replicated", 16, 8, 0, True)),      # epsilon-ish
    (dict(sparse=False, n=4096, d=64), 1, "tpu", 1, 1,
     ("pallas", "pallas-replicated", 1, 1, 0, False)),      # bucketing off
]


@pytest.mark.parametrize("shape,lanes,backend,bucket,chunks,want",
                         WORKING_CONFIGS)
def test_planner_auto_equals_static_auto(shape, lanes, backend, bucket,
                                         chunks, want):
    """The static rule, pinned: each shape class resolves to the plan
    it resolved to before the geometry search was removed."""
    p = planner.resolve_plan(**shape, bucket=bucket, chunks=chunks,
                             model_lanes=lanes, backend=backend)
    assert (p.solver, p.route, p.bucket, p.chunks, p.nnz_multiple,
            p.feature_shard) == want


def test_route_functions_equal_kernel_predicates():
    """Asked for a worker's traced rows (``n_local``), the route is the
    kernels' own predicates verbatim — the engine's misfit fallback
    relies on it."""
    for shape, lanes, _, bucket, _, _ in WORKING_CONFIGS:
        for n_local in (bucket, 3 * bucket, bucket + 1):
            plan = planner.resolve_plan(**shape, bucket=bucket,
                                        n_local=n_local, model_lanes=lanes,
                                        feature_shard=lanes > 1)
            if shape["sparse"]:
                want = kops.sparse_solver_plan(
                    n_local, shape["nnz"], shape["d"], bucket,
                    model_lanes=lanes)
                assert (plan.route, plan.reason) == \
                    (want[0], str(want[1] or "fits"))
            else:
                why = kops.dense_kernel_misfit(shape["d"], n_local, bucket)
                assert plan.route == ("xla" if why else
                                      "pallas-replicated")


# each benchmark cell's shape -> the route its trace shows (the replicated
# sparse kernel on both criteo cells, the dense kernel on higgs)
CELLS = {
    "criteo-1chip": (dict(sparse=True, n=64, d=1_000_000, nnz=40),
                     dict(bucket=8), (8, 1)),
    "criteo-dp4": (dict(sparse=True, n=256, d=1_000_000, nnz=40),
                   dict(bucket=8, lanes=4, chunks=2,
                        partition="alltoall"), (8, 2)),
    "higgs-1chip": (dict(sparse=False, n=128, d=28), dict(bucket=64),
                    (64, 1)),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_routes(cell):
    """Session on each cell's widths and engine settings (rows cut to a
    few buckets: the route reads only widths and geometry) records the
    route the cell's trace shows; on a TPU "auto" runs that kernel."""
    from repro.api.session import Session
    from repro.core import EngineConfig
    from repro.data import (make_dense_classification,
                            make_sparse_classification)

    shape, engine_kw, (bucket, chunks) = CELLS[cell]
    cfg = EngineConfig.make(**engine_kw)
    if shape["sparse"]:
        (idx, val), y, d = make_sparse_classification(
            n=shape["n"], d=shape["d"], nnz=shape["nnz"], seed=0)
        ses = Session(((idx, val), y), objective="logistic", lam=1e-3,
                      d=d, cfg=cfg)
    else:
        X, y = make_dense_classification(n=shape["n"], d=shape["d"],
                                         seed=0)
        ses = Session(np.asarray(X), np.asarray(y), objective="logistic",
                      lam=1e-3, cfg=cfg)
    plan = ses.solver_plan
    assert plan.route == "pallas-replicated"
    assert (plan.bucket, plan.chunks, plan.nnz_multiple,
            plan.feature_shard) == (bucket, chunks, 0, False)
    tpu = planner.resolve_plan(**shape, bucket=bucket, chunks=chunks,
                               backend="tpu")
    assert tpu.solver == "pallas" and tpu.route == plan.route


def test_session_bitwise_pin():
    """Off the TPU, Session "auto" trains bitwise like an explicit
    local_solver="xla", and records its plan where "xla" records none."""
    from repro.api.session import Session
    from repro.core import EngineConfig
    rng = np.random.default_rng(0)
    X = rng.normal(size=(16, 256)).astype(np.float32)
    y = np.sign(rng.normal(size=256)).astype(np.float32)

    def fit(solver):
        ses = Session(X, y, objective="logistic", lam=1e-3,
                      cfg=EngineConfig.make(local_solver=solver))
        ses.fit(max_epochs=3, tol=0.0)
        return ses

    auto, xla = fit("auto"), fit("xla")
    assert auto.solver_plan is not None and xla.solver_plan is None
    assert auto.solver_plan.solver == "xla"
    assert auto.bplan.bucket == xla.bplan.bucket
    assert auto.spec.algo.chunks == xla.spec.algo.chunks
    np.testing.assert_array_equal(np.asarray(auto.v), np.asarray(xla.v))
    np.testing.assert_array_equal(np.asarray(auto.alpha),
                                  np.asarray(xla.alpha))


def test_scale_for_dataset_pin(monkeypatch, tmp_path):
    """scale_for_dataset resolves its layout through `resolve_plan`:
    wide dense and webspam-wide sparse feature-shard, the rest keep
    features replicated, at bucket 16 and 4 chunks."""
    from repro.launch.glm import scale_for_dataset

    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    want = {"criteo-kaggle-sub": False, "epsilon": True, "higgs": False,
            "synthetic-dense": False, "synthetic-sparse": False,
            "webspam": True}
    for name, shard in want.items():
        s = scale_for_dataset(name)
        assert (s.feature_shard, s.bucket, s.chunks) == (shard, 16, 4)
    # overrides always win
    pinned = scale_for_dataset("webspam", bucket=32, chunks=2,
                               feature_shard=False)
    assert (pinned.bucket, pinned.chunks, pinned.feature_shard) == \
        (32, 2, False)


# -- the h2d byte model -----------------------------------------------------


@pytest.mark.parametrize("n,d,kw,want", [
    # fig4's streamed arm: dense d=64 over two data lanes (quick, full)
    (2048, 64, dict(workers=2), 266240.0),
    (4096, 64, dict(workers=2), 532480.0),
    # glm_analytic's shapes: webspam sharded over 4 model lanes (aligned
    # and raw row width), criteo replicated, epsilon TP and replicated
    (360_448, 16_609_280, dict(sparse=True, nnz=3728, nnz_multiple=8,
                               workers=2, model_lanes=4), 2024996864.0),
    (360_448, 16_609_280, dict(sparse=True, nnz=3727, nnz_multiple=8,
                               workers=2, model_lanes=4), 2024996864.0),
    (45_088_768, 1_048_576, dict(sparse=True, nnz=39, nnz_multiple=8,
                                 workers=8), 1826095104.0),
    (409_600, 2000, dict(workers=2, model_lanes=4), 410419200.0),
    (409_600, 2000, dict(workers=8), 409804800.0),
])
def test_streamed_transfer_bytes_pin(n, d, kw, want):
    assert planner.streamed_transfer_bytes(n, d, **kw) == want
