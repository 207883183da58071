"""Paper Fig 6: SDCA (1T / MT) vs general-purpose solvers (LBFGS, GD) —
the scikit-learn/H2O stand-ins, implemented in this repo (DESIGN.md S8)
— plus, when scikit-learn is installed, the REAL sklearn
LogisticRegression head-to-head through the estimator API (`--impl
sklearn` is implicit; the arm self-skips offline).

Metric: wall time to reach (1 + eps) x best primal value, plus the test
loss at the stop point — mirroring the paper's time-vs-test-loss frame.
The `estimator` row is `repro.api.LogisticRegression` (the paper's
solver behind the sklearn protocol), timed end-to-end like a user
would call it; its parity columns (train-score agreement with sklearn,
prediction agreement) are what CI uploads as the sklearn-parity
metrics.

The sparse arms (`sdca_sparse_xla` vs `sdca_sparse_pallas`, criteo-
shaped data) race the engine's two sparse local solvers head-to-head
at a FIXED epoch budget and emit per-solver throughput — examples/s
and a bytes-from-HBM-per-epoch model (the quantity the VMEM-resident
kernel exists to cut; DESIGN.md S11) — into the BENCH json.  On CPU
the Pallas arm runs in interpret mode, so treat its wall clock as a
smoke signal; the HBM-bytes column is the architecture-level claim.

The feature-sharded arm (`sdca_sharded_*`, webspam-shaped synthetic
with d past the replicated kernel's resident-v VMEM budget) races the
sharded-v kernel against the slice-masked XLA scan over a model-axis
mesh (DESIGN.md S12); it needs >= 2 devices and self-skips otherwise.
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.api import LogisticRegression as ReproLogReg, Session
from repro.api.session import margins
from repro.core import EngineConfig, SolverConfig
from repro.core.objectives import LOGISTIC
from repro.optim.lbfgs import glm_objective, gradient_descent, lbfgs

from .common import emit, load, make_session, parity_metrics, sklearn_logreg

HEADER = ["bench", "dataset", "solver", "wall_s", "primal", "test_loss",
          "speedup_vs_lbfgs", "examples_per_s", "hbm_bytes_epoch",
          "score", "score_sklearn", "predict_agree"]
LAM = 1e-3


def _test_loss(v, Xt, yt):
    m = Xt.T @ v
    return float(jnp.mean(LOGISTIC.loss(m, yt)))


# -- sparse solver arms: XLA gather/scatter scan vs the Pallas kernel -------

SPARSE_CHUNKS = 2
SPARSE_LANES = 4
SPARSE_BUCKET = 8


def _sparse_hbm_bytes(n: int, nnz: int, d: int, solver: str,
                      chunks: int = SPARSE_CHUNKS) -> float:
    """Bytes each sparse solver moves through HBM per epoch (model).

    Both stream the (n, nnz) idx/val rows once (4+4 B/entry).  The XLA
    scan's carry is the full shared vector, so every coordinate also
    pays an nnz-wide gather + read-modify-write scatter against HBM-
    resident v (3 x 4 B/entry).  The Pallas kernel pins v in VMEM for
    the whole sub-epoch; v crosses HBM once per chunk sync (in + out).
    """
    data = n * nnz * 8
    if solver == "pallas":
        return float(data + chunks * d * 4 * 2)
    return float(data + n * nnz * 4 * 3)


def _sparse_rows(quick: bool) -> list[dict]:
    rows = []
    epochs = 2 if quick else 6
    data = load("criteo")                  # criteo-kaggle-sub subsample
    idx, val, y = data["X"][0], data["X"][1], data["y"]
    n, nnz = idx.shape
    blk = SPARSE_LANES * SPARSE_LANES * SPARSE_CHUNKS * SPARSE_BUCKET
    ntr = (int(n * 0.8) // blk) * blk
    tr = dict(X=(idx[:ntr], val[:ntr]), y=y[:ntr], d=data["d"],
              sparse=True)
    te = (jnp.asarray(idx[ntr:]), jnp.asarray(val[ntr:]))
    yte = jnp.asarray(y[ntr:])

    for solver in ("xla", "pallas"):
        cfg = EngineConfig.make(
            lanes=SPARSE_LANES, bucket=SPARSE_BUCKET, chunks=SPARSE_CHUNKS,
            partition="dynamic", deterministic=True, local_solver=solver)
        ses = Session(tr["X"], tr["y"], objective="logistic", lam=LAM,
                      cfg=cfg, d=tr["d"], pad=False)
        ses._epoch_fn(ses.alpha, ses.v, jnp.int32(0))    # warm the jit
        t0 = time.perf_counter()
        ses.fit(max_epochs=epochs, tol=0.0)
        wall = time.perf_counter() - t0
        rows.append(dict(
            bench="fig6", dataset="criteo-sparse",
            solver=f"sdca_sparse_{solver}", wall_s=wall,
            primal=ses.primal(),
            test_loss=float(jnp.mean(LOGISTIC.loss(
                margins(ses.v, te), yte))),
            examples_per_s=ntr * epochs / wall,
            hbm_bytes_epoch=_sparse_hbm_bytes(ntr, nnz, tr["d"], solver)))
    return rows


# -- feature-sharded sparse arm: webspam-shape d on a model-axis mesh -------

SHARDED_D = 2_101_248        # past the replicated kernel's resident-v
                             # VMEM budget (2_097_152 f32 rows), so only
                             # the sharded kernel or the scan can run it
SHARDED_NNZ = 64             # webspam's offline fallback row width
SHARDED_N = 128
SHARDED_LANES = 2            # model-axis lanes


def _sharded_hbm_bytes(n: int, nnz: int, d: int, M: int,
                       solver: str) -> float:
    """Per-device HBM bytes/epoch for the feature-sharded arms (model).

    Every model lane streams the full (n, nnz) idx/val rows (the data
    is replicated over the model axis).  The sharded Pallas kernel
    keeps only its d/M slice resident: per bucket it round-trips the
    slice (in + out — the per-bucket pallas_call boundary forces a
    full-block DMA, unlike the replicated kernel's grid-resident v)
    and receives the all-gathered (M, B, nnz) f32 working set; the
    full v crosses HBM once per chunk sync.  The slice-masked XLA scan
    pays the HBM-resident-v gather/scatter exactly like the unsharded
    scan, plus the same syncs.  At bench scale the slice round-trip
    dominates, so the sharded kernel's bytes column exceeds the scan's
    — the column is here to make that cost structure visible, not to
    flatter the kernel; its win is VMEM-resident compute (examples/s
    on real TPUs) on shapes the replicated kernel cannot run AT ALL.
    """
    from repro.kernels.ops import sparse_slice_width
    data = n * nnz * 8
    sync = SPARSE_CHUNKS * d * 4 * 2
    if solver == "pallas":
        d_loc = sparse_slice_width(d, M)
        nb = n // SPARSE_BUCKET
        return float(data + nb * d_loc * 4 * 2
                     + nb * M * SPARSE_BUCKET * nnz * 4 + sync)
    return float(data + n * nnz * 4 * 3 + sync)


def _sharded_sparse_rows(quick: bool) -> list[dict]:
    """Race the feature-sharded sparse kernel vs the slice-masked XLA
    scan on a webspam-shaped synthetic (d past the replicated kernel's
    resident-v budget) over a (data=1, model=2) mesh.  Needs >= 2
    devices — the bench-smoke CI job forces host devices; runs with
    fewer skip the arm (compare.py's workload-version gate keeps such
    runs from being diffed against 2-device baselines)."""
    import jax
    from repro.data import make_sparse_classification
    from repro.launch.glm import GLMScale, make_sparse_epoch
    from repro.launch.mesh import make_host_mesh

    if jax.device_count() < SHARDED_LANES:
        print(f"# fig6 sharded arm skipped: "
              f"{jax.device_count()} device(s) < {SHARDED_LANES}")
        return []
    epochs = 1 if quick else 2
    n, d, nnz = SHARDED_N, SHARDED_D, SHARDED_NNZ
    (idx, val), y, _ = make_sparse_classification(n=n, d=d, nnz=nnz,
                                                  seed=6)
    idx, val, y = (jnp.asarray(t) for t in (idx, val, y))
    mesh = make_host_mesh(pod=1, data=1, model=SHARDED_LANES)
    rows = []
    for solver in ("xla", "pallas"):
        sc = GLMScale("webspam-sharded", "sparse", n=n, d=d, nnz=nnz,
                      bucket=SPARSE_BUCKET, chunks=SPARSE_CHUNKS,
                      lam=LAM, compress_pod=False, deterministic=True,
                      local_solver=solver, feature_shard=True)
        with mesh:
            ep = jax.jit(make_sparse_epoch(sc, mesh))
            jax.block_until_ready(                         # warm the jit
                ep(idx, val, y, jnp.zeros(n), jnp.zeros(d), jnp.int32(0)))
            st = (idx, val, y, jnp.zeros(n), jnp.zeros(d))
            t0 = time.perf_counter()
            for e in range(epochs):
                st = ep(*st, jnp.int32(e))
            jax.block_until_ready(st)
            wall = time.perf_counter() - t0
        v = st[4]
        rows.append(dict(
            bench="fig6", dataset="webspam-sharded",
            solver=f"sdca_sharded_{solver}", wall_s=wall,
            primal=float(jnp.mean(LOGISTIC.loss(margins(v, (idx, val)),
                                                y))
                         + LAM / 2 * jnp.vdot(v, v)),
            examples_per_s=n * epochs / wall,
            hbm_bytes_epoch=_sharded_hbm_bytes(n, nnz, d, SHARDED_LANES,
                                               solver)))
    return rows


def run(quick: bool = False):
    rows = []
    names = ["epsilon"] if quick else ["higgs", "epsilon"]
    for name in names:
        data = load(name)
        if data["sparse"]:
            continue                      # LBFGS baseline is dense-only
        X, y = jnp.asarray(data["X"]), jnp.asarray(data["y"])
        n = y.shape[0]
        # train split must divide into (bucket=8 x lanes=16) blocks
        ntr = (int(n * 0.8) // 128) * 128
        Xtr, ytr = X[:, :ntr], y[:ntr]
        Xte, yte = X[:, ntr:], y[ntr:]
        tr_data = dict(X=Xtr, y=ytr, d=int(Xtr.shape[0]), sparse=False)

        vg = glm_objective(LOGISTIC, Xtr, ytr, LAM)
        t0 = time.perf_counter()
        w_l, hist_l = lbfgs(vg, jnp.zeros(Xtr.shape[0]),
                            max_iters=150 if quick else 400, tol=1e-6)
        t_lbfgs = time.perf_counter() - t0

        t0 = time.perf_counter()
        w_g, hist_g = gradient_descent(vg, jnp.zeros(Xtr.shape[0]),
                                       max_iters=100 if quick else 300)
        t_gd = time.perf_counter() - t0

        results = {"lbfgs": (t_lbfgs, float(vg(w_l)[0]),
                             _test_loss(w_l, Xte, yte)),
                   "gd": (t_gd, float(vg(w_g)[0]),
                          _test_loss(w_g, Xte, yte))}

        for solver, cfg in (
            ("sdca_1T", SolverConfig(pods=1, lanes=1, bucket=8)),
            ("sdca_MT", SolverConfig(pods=1, lanes=16, bucket=8,
                                     partition="dynamic")),
        ):
            ses = make_session(tr_data, cfg, lam=LAM)
            ses._epoch_fn(ses.alpha, ses.v, jnp.int32(0))   # warm jit
            t0 = time.perf_counter()
            ses.fit(max_epochs=60, tol=1e-4)
            wall = time.perf_counter() - t0
            results[solver] = (wall, ses.primal(),
                               _test_loss(jnp.asarray(ses.v), Xte, yte))

        # the estimator arm: end-to-end through the public API (no jit
        # pre-warm — this is the latency a fresh sklearn user sees)
        est = ReproLogReg(lam=LAM, max_epochs=60, tol=1e-4, lanes=16,
                          bucket=8, partition="dynamic")
        t0 = time.perf_counter()
        est.fit(np.asarray(Xtr).T, np.asarray(ytr))
        wall_est = time.perf_counter() - t0
        # primal evaluated on the UNPADDED objective so rows compare
        results["estimator"] = (wall_est,
                                float(vg(jnp.asarray(est.coef_))[0]),
                                _test_loss(jnp.asarray(est.coef_),
                                           Xte, yte))

        sk = sklearn_logreg(tr_data, lam=LAM,
                            max_iter=100 if quick else 400)
        parity: dict[str, dict] = {}
        if sk is not None:
            w_sk = jnp.asarray(sk["clf"].coef_.ravel())
            results["sklearn"] = (sk["wall_s"], float(vg(w_sk)[0]),
                                  _test_loss(w_sk, Xte, yte))
            est_arm = dict(est=est,
                           score=float(est.score(sk["X"], sk["y"])),
                           inputs=(sk["X"], sk["y"]))
            # parity rides on the ESTIMATOR row (score = ours), keyed
            # the same way fig3 does, so CI's drift tracking compares
            # like-for-like records across figures
            parity["estimator"] = parity_metrics(est_arm, sk)

        for solver, (wall, primal, tl) in results.items():
            rows.append(dict(bench="fig6", dataset=name, solver=solver,
                             wall_s=wall, primal=primal, test_loss=tl,
                             speedup_vs_lbfgs=results["lbfgs"][0] / wall,
                             **parity.get(solver, {})))
    rows.extend(_sparse_rows(quick))
    rows.extend(_sharded_sparse_rows(quick))
    return emit(rows, HEADER)


if __name__ == "__main__":
    run()
