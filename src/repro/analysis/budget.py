"""Static VMEM budget audit: plans vs the kernels' own estimators.

`audit_plan` is an INDEPENDENT re-derivation of the feasibility
arithmetic: given a plan that claims a Pallas route, it recomputes the
registered VMEM estimator for the plan's geometry and checks it
against the topology's budgets directly — it does not trust
`planner._plan_feasible` or the route verdict baked into the plan.
On a clean tree the sweep finds nothing, because `candidate_plans`
attaches routes through `ops.sparse_solver_plan`/`dense_kernel_misfit`
and those share the estimators; the audit exists to catch DRIFT — an
estimator change that the routing predicates stopped mirroring, a
hand-edited plan cache, or a forged plan (the mutation self-test).

`run_budget_audit` sweeps every registry workload (sub AND real
shapes) x TPU topologies (model_lanes 1/2/8) x the planner's full
candidate geometry enumeration.
"""
from __future__ import annotations

from typing import Optional

from . import rules
from .rules import Finding

__all__ = ["audit_plan", "run_budget_audit"]

#: model-lane counts swept per workload (1 = no model axis; 2 and 8
#: bracket the v5e configurations the launch scripts target).
MODEL_LANES = (1, 2, 8)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def audit_plan(sig, topo, plan) -> list[Finding]:
    """VMEM-PLAN-BUDGET for one (workload, topology, plan) triple.

    Re-evaluates the claiming kernel's estimator for the plan's
    geometry against the topology budgets.  xla-routed plans are
    always fine (HBM-resident v scan has no VMEM contract).
    """
    from repro.kernels import ops, sdca_bucket, sdca_sparse_bucket

    if plan.solver != "pallas" or plan.route == "xla":
        return []
    found: list[Finding] = []
    where = "src/repro/core/planner.py:1"
    case = (f"{sig.name or 'workload'}(n={sig.n},d={sig.d},"
            f"nnz={sig.nnz})/M={topo.model_lanes}")

    def emit(msg: str) -> None:
        found.append(Finding(rules.VMEM_PLAN_BUDGET, msg, where=where,
                             case=case))

    B = plan.bucket
    if sig.sparse:
        nnz = _round_up(max(sig.nnz, 1), plan.nnz_multiple) \
            if plan.nnz_multiple else sig.nnz
        if plan.route == "pallas-sharded":
            if not plan.feature_shard or topo.model_lanes <= 1:
                emit(f"plan claims route=pallas-sharded without a "
                     f"model axis (feature_shard={plan.feature_shard}, "
                     f"model_lanes={topo.model_lanes})")
                return found
            d_eff = ops.sparse_slice_width(sig.d, topo.model_lanes)
            need = sdca_sparse_bucket.vmem_bytes_estimate_sharded(
                B, nnz, d_eff)
            label = f"sharded slice d_loc={d_eff}"
        else:
            d_eff = sig.d
            need = sdca_sparse_bucket.vmem_bytes_estimate(B, nnz, sig.d)
            label = f"replicated d={sig.d}"
        v_bytes = sdca_sparse_bucket.v_bytes(d_eff)
        if v_bytes > topo.v_budget():
            emit(f"{plan.route} plan's resident v ({label}, "
                 f"{v_bytes} B) exceeds the {topo.v_budget()}-byte "
                 f"resident-v budget")
        if need > topo.total_budget():
            emit(f"{plan.route} plan needs ~{need} B of VMEM for "
                 f"(B={B}, nnz={nnz}, {label}); budget is "
                 f"{topo.total_budget()} B")
    else:
        B_pad = _round_up(max(B, 8), 8)
        if B_pad > sdca_bucket.MAX_BUCKET:
            emit(f"dense plan bucket={B} exceeds the kernel recursion "
                 f"cap B <= {sdca_bucket.MAX_BUCKET}")
        need = sdca_bucket.vmem_bytes_estimate(B_pad, sig.d)
        if need > topo.total_budget():
            emit(f"dense plan needs ~{need} B of VMEM for (B={B_pad}, "
                 f"d={sig.d}); budget is {topo.total_budget()} B")
    return found


def _signatures():
    from repro.core.planner import WorkloadSignature
    from repro.data.registry import REGISTRY
    sigs = []
    for name in sorted(REGISTRY):
        spec = REGISTRY[name]
        sparse = spec.kind == "sparse"
        sigs.append(WorkloadSignature(
            n=spec.sub_n, d=spec.sub_d, nnz=spec.sub_nnz or 0,
            sparse=sparse, name=f"{name}-sub"))
        if (spec.full_n, spec.full_d) != (spec.sub_n, spec.sub_d):
            sigs.append(WorkloadSignature(
                n=spec.full_n, d=spec.full_d, nnz=spec.nnz or 0,
                sparse=sparse, name=name))
    return sigs


def run_budget_audit(log=None) -> tuple[list[Finding], int]:
    """Sweep registry workloads x TPU topologies x candidate plans.

    -> (findings, plans_swept).
    """
    from repro.core.planner import Topology, candidate_plans

    found: list[Finding] = []
    n_plans = 0
    for sig in _signatures():
        for lanes in MODEL_LANES:
            topo = Topology(backend="tpu", device_count=max(lanes, 1),
                            model_lanes=lanes)
            plans = candidate_plans(sig, topo)
            n_plans += len(plans)
            for plan in plans:
                found += audit_plan(sig, topo, plan)
    if log is not None:
        log(f"  budget: {n_plans} candidate plans swept, "
            f"{len(found)} finding(s)")
    return found, n_plans
