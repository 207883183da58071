"""Static VMEM budget audit: plans vs the kernels' own estimators.

`audit_plan` is an INDEPENDENT re-derivation of the feasibility
arithmetic: given a plan that claims a Pallas route, it recomputes the
registered VMEM estimator for the plan's geometry and checks it
against the kernels' budgets directly — it does not trust the route
verdict baked into the plan.  On a clean tree the sweep finds nothing,
because `core.planner.resolve_plan` attaches routes through
`ops.sparse_solver_plan`/`dense_kernel_misfit` and those share the
estimators; the audit exists to catch DRIFT — an estimator change that
the routing predicates stopped mirroring, or a forged plan (the
mutation self-test).

`run_budget_audit` sweeps every registry workload (sub AND real
shapes) x model lanes 1/2/8 x its own explicit grid of geometries
(`BUCKETS` x `CHUNKS` x row-width padding x feature layout), each plan
built by `resolve_plan`.
"""
from __future__ import annotations

from . import rules
from .rules import Finding

__all__ = ["audit_plan", "run_budget_audit"]

#: model-lane counts swept per workload (1 = no model axis; 2 and 8
#: bracket the v5e configurations the launch scripts target).
MODEL_LANES = (1, 2, 8)
#: bucket sizes swept (f32 sublane multiples up to the sparse kernels'
#: practical range; the dense kernel caps at 512).
BUCKETS = (8, 16, 32, 64, 128)
#: sync intervals swept (v reductions per epoch).
CHUNKS = (1, 2, 4, 8)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def audit_plan(plan, *, sparse: bool, n: int, d: int, nnz: int = 0,
               model_lanes: int = 1, name: str = "") -> list[Finding]:
    """VMEM-PLAN-BUDGET for one plan of a workload of shape
    (``sparse``, ``n``, ``d``, ``nnz``) over ``model_lanes``.

    Re-evaluates the claiming kernel's estimator for the plan's
    geometry against the kernels' budgets.  xla-routed plans are
    always fine (HBM-resident v scan has no VMEM contract).
    """
    from repro.kernels import ops, sdca_bucket, sdca_sparse_bucket

    if plan.solver != "pallas" or plan.route == "xla":
        return []
    found: list[Finding] = []
    where = "src/repro/core/planner.py:1"
    case = f"{name or 'workload'}(n={n},d={d},nnz={nnz})/M={model_lanes}"
    v_budget = sdca_sparse_bucket.V_VMEM_BUDGET_BYTES
    total_budget = sdca_sparse_bucket.TOTAL_VMEM_BUDGET_BYTES

    def emit(msg: str) -> None:
        found.append(Finding(rules.VMEM_PLAN_BUDGET, msg, where=where,
                             case=case))

    B = plan.bucket
    if sparse:
        nnz = _round_up(max(nnz, 1), plan.nnz_multiple) \
            if plan.nnz_multiple else nnz
        if plan.route == "pallas-sharded":
            if not plan.feature_shard or model_lanes <= 1:
                emit(f"plan claims route=pallas-sharded without a "
                     f"model axis (feature_shard={plan.feature_shard}, "
                     f"model_lanes={model_lanes})")
                return found
            d_eff = ops.sparse_slice_width(d, model_lanes)
            need = sdca_sparse_bucket.vmem_bytes_estimate_sharded(
                B, nnz, d_eff)
            label = f"sharded slice d_loc={d_eff}"
        else:
            d_eff = d
            need = sdca_sparse_bucket.vmem_bytes_estimate(B, nnz, d)
            label = f"replicated d={d}"
        v_bytes = sdca_sparse_bucket.v_bytes(d_eff)
        if v_bytes > v_budget:
            emit(f"{plan.route} plan's resident v ({label}, "
                 f"{v_bytes} B) exceeds the {v_budget}-byte "
                 f"resident-v budget")
        if need > total_budget:
            emit(f"{plan.route} plan needs ~{need} B of VMEM for "
                 f"(B={B}, nnz={nnz}, {label}); budget is "
                 f"{total_budget} B")
    else:
        B_pad = _round_up(max(B, 8), 8)
        if B_pad > sdca_bucket.MAX_BUCKET:
            emit(f"dense plan bucket={B} exceeds the kernel recursion "
                 f"cap B <= {sdca_bucket.MAX_BUCKET}")
        need = sdca_bucket.vmem_bytes_estimate(B_pad, d)
        if need > total_budget:
            emit(f"dense plan needs ~{need} B of VMEM for (B={B_pad}, "
                 f"d={d}); budget is {total_budget} B")
    return found


def _workloads() -> list[dict]:
    """Every registry workload, sub-sampled AND real shape."""
    from repro.data.registry import REGISTRY
    out = []
    for name in sorted(REGISTRY):
        spec = REGISTRY[name]
        sparse = spec.kind == "sparse"
        out.append(dict(sparse=sparse, n=spec.sub_n, d=spec.sub_d,
                        nnz=spec.sub_nnz or 0, name=f"{name}-sub"))
        if (spec.full_n, spec.full_d) != (spec.sub_n, spec.sub_d):
            out.append(dict(sparse=sparse, n=spec.full_n, d=spec.full_d,
                            nnz=spec.nnz or 0, name=name))
    return out


def _grid(w: dict, model_lanes: int):
    """The swept geometries of one workload: every (bucket, chunks)
    whose chunks divide the bucket count, unpadded and (for an
    unaligned row width) 8-padded rows, the replicated layout and —
    with model lanes, or where the static rule shards — the
    feature-sharded one."""
    from repro.core.planner import feature_shard_default
    zmults = (0, 8) if w["sparse"] and w["nnz"] % 8 else (0,)
    layouts = [False]
    if model_lanes > 1 or feature_shard_default(w["sparse"], w["d"]):
        layouts.append(True)
    for B in BUCKETS:
        for C in CHUNKS:
            if max(w["n"] // B, 1) % C:
                continue
            for z in zmults:
                for shard in layouts:
                    yield dict(bucket=B, chunks=C, nnz_multiple=z,
                               feature_shard=shard)


def run_budget_audit(log=None) -> tuple[list[Finding], int]:
    """Sweep registry workloads x model lanes x the geometry grid,
    each plan resolved by `core.planner.resolve_plan` for a TPU.

    -> (findings, plans_swept).
    """
    from repro.core.planner import resolve_plan

    found: list[Finding] = []
    n_plans = 0
    for w in _workloads():
        shape = {k: w[k] for k in ("sparse", "n", "d", "nnz")}
        for lanes in MODEL_LANES:
            for geom in _grid(w, lanes):
                plan = resolve_plan(**shape, **geom, model_lanes=lanes,
                                    backend="tpu")
                n_plans += 1
                found += audit_plan(plan, **shape, model_lanes=lanes,
                                    name=w["name"])
    if log is not None:
        log(f"  budget: {n_plans} plans swept, "
            f"{len(found)} finding(s)")
    return found, n_plans
