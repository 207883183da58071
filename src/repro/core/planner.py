"""The one place that chooses the local solver's route (DESIGN.md S13).

`resolve_plan` maps what a caller can observe — sparse or dense, n, d,
nnz, bucket, chunks, model lanes, backend — to a `SolverPlan`: the
solver ``local_solver="auto"`` runs, the kernel variant (route) and the
geometry it runs with.  The rule is static:

  * bucket from the caller, else `bucketing.choose_bucket_size(n, d)`;
  * chunks from the caller, else 1;
  * the feature layout from the caller, else `feature_shard_default`;
  * the route from the kernels' own predicates
    (`kernels.ops.sparse_solver_plan`, `dense_kernel_misfit`), so a plan
    can never loosen what a kernel accepts;
  * solver "pallas" where the route is a kernel and the backend is a
    TPU (`auto_solver`), else "xla".

Its callers: `Session` at construction, `launch/glm.py
scale_for_dataset`, the engine's trace-time misfit fallback
(`engine.make_local_solver`) and the static VMEM auditor
(`analysis/budget.py`).  `streamed_transfer_bytes` is the one model of
the host->device bytes a streamed epoch ships.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

__all__ = ["H2D_BW", "SolverPlan", "auto_solver", "feature_shard_default",
           "resolve_plan", "streamed_transfer_bytes"]

#: Host->device link bandwidth (bytes/s) that turns
#: `streamed_transfer_bytes` into seconds in the roofline table.  A
#: PCIe-class figure, deliberately conservative: TPU hosts feed chips
#: over PCIe, ~50x slower than HBM.  The ONE definition —
#: `launch/mesh.py` re-exports it.
H2D_BW = 16e9


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@dataclasses.dataclass(frozen=True)
class SolverPlan:
    """One resolved route + geometry.

    ``solver`` is what ``local_solver="auto"`` resolves to ("pallas" |
    "xla"); ``route`` the kernel variant the shapes fit
    ("pallas-replicated" | "pallas-sharded" | "xla"), whatever the
    backend.  ``reason`` carries the misfit string for "xla" routes and
    "fits" otherwise; ``reason_code`` its stable
    `kernels.ops.MisfitCode` ("" when the geometry fits) so tools can
    key on the verdict without parsing prose.
    """
    solver: str
    route: str
    bucket: int
    chunks: int
    nnz_multiple: int             # 0 = no row-width padding needed
    feature_shard: bool
    reason: str = ""
    reason_code: str = ""


def auto_solver(backend: Optional[str] = None) -> str:
    """What ``local_solver="auto"`` means before the shapes are known:
    "pallas" on a TPU backend (dense AND sparse — both kernels exist),
    "xla" everywhere else.  ``backend`` defaults to the live jax one."""
    if backend is None:
        import jax
        backend = jax.default_backend()
    return "pallas" if backend == "tpu" else "xla"


def feature_shard_default(sparse: bool, d: int) -> bool:
    """Shard features over 'model' exactly when the replicated shared
    vector cannot fit the sparse kernel's resident-v VMEM budget
    (sparse), or when d is TP-wide (dense, d >= 512)."""
    if sparse:
        from repro.kernels.sdca_sparse_bucket import (V_VMEM_BUDGET_BYTES,
                                                      v_bytes)
        return v_bytes(d) > V_VMEM_BUDGET_BYTES
    return d >= 512


def resolve_plan(*, sparse: bool, n: int, d: int, nnz: int = 0,
                 bucket: Optional[int] = None,
                 chunks: Optional[int] = None,
                 nnz_multiple: Optional[int] = None,
                 model_lanes: int = 1,
                 feature_shard: Optional[bool] = None,
                 n_local: Optional[int] = None,
                 backend: Optional[str] = None) -> SolverPlan:
    """Shape + platform -> `SolverPlan` (the module docstring's rule).

    ``nnz`` is the padded-CSR row width (0 for dense), rounded up to
    ``nnz_multiple`` when one is given.  A sparse feature-sharded
    layout routes over ``model_lanes`` lanes, a replicated one over
    one.  ``n_local`` is the row count one worker solves; it defaults
    to the bucket, since set-up pads every worker's rows to a bucket
    multiple — the engine passes its traced count.  ``backend``
    defaults to the live jax one.
    """
    from repro.core.bucketing import choose_bucket_size
    from repro.kernels import ops as kops
    B = choose_bucket_size(n, d) if bucket is None else bucket
    C = chunks or 1
    zmult = nnz_multiple or 0
    shard = (feature_shard_default(sparse, d) if feature_shard is None
             else feature_shard)
    rows = B if n_local is None else n_local
    if sparse:
        width = _round_up(max(nnz, 1), zmult) if zmult else nnz
        route, reason = kops.sparse_solver_plan(
            rows, width, d, B, model_lanes=model_lanes if shard else 1)
    else:
        reason = kops.dense_kernel_misfit(d, rows, B)
        route = "xla" if reason else "pallas-replicated"
    solver = "xla" if route == "xla" else auto_solver(backend)
    return SolverPlan(
        solver=solver, route=route, bucket=B, chunks=C,
        nnz_multiple=zmult, feature_shard=shard,
        reason=str(reason or "fits"),
        reason_code=getattr(reason, "code", ""))


def streamed_transfer_bytes(n: int, d: int, *, sparse: bool = False,
                            nnz: int = 0, nnz_multiple: int = 0,
                            workers: int = 1,
                            model_lanes: int = 1) -> float:
    """Modeled host->device bytes per device per streamed epoch.

    The ONE h2d byte model (DESIGN.md S16): `launch/glm.py
    glm_analytic(streamed=True)` and the fig4 benchmark report this
    quantity.  ``workers`` is the example-parallel worker count,
    ``model_lanes`` the lanes the features are sharded over (1 =
    replicated).  Mirrors what `engine.MeshChunkFeed` ships:

      dense replicated   n_loc * d * 4            (each worker's X cols)
      dense TP           n_loc * d_loc * 4        (device_put slices rows)
      sparse replicated  n_loc * nnz * 8          (idx + val, full rows)
      sparse sharded     n_loc * w * 12           (slice-compacted
                         idx/val/pos, w ~= the per-lane share of the
                         row width ceiled to the lane multiple — the
                         ~M-fold per-lane saving; the real feed's w is
                         data-dependent, this is the uniform estimate)

    plus 4 bytes/example of labels everywhere.
    """
    n_loc = max(n // max(workers, 1), 1)
    y_bytes = n_loc * 4
    if sparse:
        width = max(_round_up(max(nnz, 1), nnz_multiple)
                    if nnz_multiple else nnz, 1)
        if model_lanes > 1:
            mult = nnz_multiple or 8
            w = min(_round_up(-(-width // model_lanes), mult), width)
            return float(n_loc * w * 12 + y_bytes)
        return float(n_loc * width * 8 + y_bytes)
    d_loc = -(-d // model_lanes) if model_lanes > 1 else d
    return float(n_loc * d_loc * 4 + y_bytes)
