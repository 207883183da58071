"""System-aware auto-tuning of bucket/tile geometry (DESIGN.md S13).

The source paper shows that per-epoch speed and convergence trade off
through the bucket/partition geometry; its follow-up **SySCD: A
System-Aware Parallel Coordinate Descent Algorithm** (PAPERS.md) closes
that gap by making bucket size, worker count, and data layout functions
of the *machine* instead of config constants.  This module is that
planner for the TPU re-derivation: given a workload signature
(n, d, nnz, sparsity, dtype) and a topology (backend, device count,
model lanes, VMEM budgets), it

  1. enumerates candidate geometries — (bucket B, chunks,
     nnz_multiple, replicated-vs-feature-sharded layout) — and filters
     them through the EXISTING feasibility predicates
     (`kernels.ops.sparse_solver_plan` / `dense_kernel_misfit`, i.e.
     the kernels' own VMEM/alignment models; the planner can never
     loosen them);
  2. scores survivors with an analytic bytes-per-effective-epoch model
     (HBM traffic per epoch x a convergence multiplier for shuffle
     granularity and sync interval — the SySCD trade-off made
     explicit);
  3. optionally refines the top candidates with a few *timed probe
     epochs* (`probe_plans`) when the caller can provide a
     `probe_fn(plan) -> seconds`;
  4. emits a `SolverPlan`, cached on disk per (dataset fingerprint,
     topology fingerprint, PLAN_VERSION) alongside the tile cache
     (`data.registry.cache_root()/plans`), so the search is paid once
     per workload x machine.

Never-regress contract (the PR-4 rule, extended): every plan the
planner emits must pass the same misfit pre-checks the engine's
backend-picked "auto" path applies, and any planner failure — bad
cache file, version skew, search exception — falls back WARN-AND-SAFE
to today's static resolution.  ``$REPRO_PLAN`` is the escape hatch:

    $REPRO_PLAN=off      bypass the planner everywhere (static rules)
    $REPRO_PLAN=on       validate/route/cache; keep static geometry
                         unless it is infeasible (default)
    $REPRO_PLAN=search   let the analytic model pick the geometry
    $REPRO_PLAN=probe    search + timed probe epochs (needs a probe_fn)

Under the default ``on`` mode the planner's geometry is BITWISE
identical to the static rules on every previously-working config
(pinned by tests/test_planner.py): it only repairs geometries the
static rules would reject, and it owns the layout boundary decisions
that used to be hardcoded (`launch/glm.py scale_for_dataset`'s
feature-shard flip).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
import pathlib
import warnings
from typing import Callable, Optional

__all__ = [
    "PLAN_VERSION", "H2D_BW", "WorkloadSignature", "Topology",
    "SolverPlan", "plan_mode", "static_plan", "candidate_plans",
    "plan_cost", "search_plans", "probe_plans", "resolve_plan",
    "plan_cache_dir", "load_cached_plan", "store_plan", "route_sparse",
    "route_dense", "feature_shard_default", "streamed_transfer_bytes",
]

#: Bump when the plan schema, the search space, or the cost model
#: changes meaning: cached plans from older versions are ignored (the
#: key embeds the version, and `load_cached_plan` re-checks the stored
#: field), so a bump invalidates cleanly — same discipline as
#: `data.cache.CACHE_VERSION`.
PLAN_VERSION = 1

#: Candidate bucket sizes (f32 sublane multiples; the dense kernel caps
#: at MAX_BUCKET=512 and the misfit predicates enforce it).
BUCKET_CANDIDATES = (8, 16, 32, 64, 128)
#: Candidate sync intervals (v reductions per epoch).
CHUNK_CANDIDATES = (1, 2, 4, 8)

# -- convergence-multiplier constants (the SySCD trade-off, made
# explicit so docs/tuning.md can cite them).  Larger buckets coarsen
# the per-epoch shuffle (the paper's only residual bucketing cost);
# fewer chunks mean staler v replicas between syncs when several
# workers add deltas.  Both are mild, so the multipliers are mild —
# the analytic score is a RANKING device, refined by probe epochs when
# available, not a convergence proof.
CONV_BUCKET_COST = 0.02       # per doubling of B above 8
CONV_SYNC_COST = 0.10         # x (workers-1)/workers / chunks

#: Host->device link bandwidth (bytes/s) used to weigh streamed-ingest
#: transfer bytes against HBM traffic in `plan_cost` and to turn
#: `streamed_transfer_bytes` into seconds in the roofline table.  A
#: PCIe-class figure, deliberately conservative: TPU hosts feed chips
#: over PCIe, ~50x slower than HBM, which is exactly why streamed plans
#: must score ingest bytes separately from on-chip traffic.  The ONE
#: definition — `launch/mesh.py` and the benchmarks re-export it.
H2D_BW = 16e9

#: HBM bandwidth assumed by the cost model's streamed-ingest weighting
#: (matches `launch/mesh.py`'s roofline constant for TPU v5p-class
#: chips; only the RATIO to H2D_BW enters the score).
_HBM_BW = 819e9


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# ---------------------------------------------------------------------------
# Inputs: workload signature + machine topology
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class WorkloadSignature:
    """Everything about the DATA that shapes the plan.

    ``nnz`` is the padded-CSR row width (0 for dense), ``density`` an
    optional observed nonzero fraction (informational — feasibility
    only depends on the padded width).  ``name`` carries the registry
    name when known so cached plans are human-findable on disk.
    ``streamed`` marks out-of-core workloads whose chunks arrive over
    the host link each epoch: `plan_cost` then weighs the per-epoch
    H2D bytes (HBM-equivalent via the bandwidth ratio) so geometry
    choices see the ingest cost; resident workloads score unchanged.
    """
    n: int
    d: int
    nnz: int = 0
    sparse: bool = False
    dtype_bytes: int = 4
    name: str = ""
    density: float = 0.0
    streamed: bool = False

    def fingerprint(self) -> str:
        """Stable hash of the plan-relevant fields (n/d/nnz/kind).

        ``streamed`` joins the key only when set, so every resident
        fingerprint (and its cached plans) is byte-identical to
        pre-streaming versions.
        """
        key = (f"{self.name}|n{self.n}|d{self.d}|z{self.nnz}"
               f"|s{int(self.sparse)}|b{self.dtype_bytes}"
               + ("|st1" if self.streamed else ""))
        return hashlib.sha1(key.encode()).hexdigest()[:12]


@dataclasses.dataclass(frozen=True)
class Topology:
    """Everything about the MACHINE that shapes the plan.

    VMEM budgets default to the kernels' own constants so the planner
    and the kernels can never disagree about feasibility; they are
    fields (not imports at use sites) so tests can probe exact
    boundaries.
    """
    backend: str                  # "tpu" | "cpu" | "gpu"
    device_count: int = 1
    pods: int = 1
    lanes: int = 1
    model_lanes: int = 1
    vmem_v_budget: int = 0        # 0 = kernel default
    vmem_total_budget: int = 0

    @classmethod
    def detect(cls, spec=None, *, model_lanes: int = 1) -> "Topology":
        """Topology from the live jax backend (+ an EngineConfig's
        deployment layer when given)."""
        import jax
        pods = lanes = 1
        if spec is not None:
            dep = getattr(spec, "deployment", spec)
            pods = getattr(dep, "pods", 1)
            lanes = getattr(dep, "lanes", 1)
        return cls(backend=jax.default_backend(),
                   device_count=jax.device_count(),
                   pods=pods, lanes=lanes, model_lanes=model_lanes)

    @property
    def workers(self) -> int:
        return max(self.pods * self.lanes, 1)

    def v_budget(self) -> int:
        if self.vmem_v_budget:
            return self.vmem_v_budget
        from repro.kernels.sdca_sparse_bucket import V_VMEM_BUDGET_BYTES
        return V_VMEM_BUDGET_BYTES

    def total_budget(self) -> int:
        if self.vmem_total_budget:
            return self.vmem_total_budget
        from repro.kernels.sdca_sparse_bucket import TOTAL_VMEM_BUDGET_BYTES
        return TOTAL_VMEM_BUDGET_BYTES

    def fingerprint(self) -> str:
        """Stable hash of the plan-relevant machine facts."""
        key = (f"{self.backend}|c{self.device_count}|p{self.pods}"
               f"|l{self.lanes}|m{self.model_lanes}"
               f"|v{self.v_budget()}|t{self.total_budget()}")
        return hashlib.sha1(key.encode()).hexdigest()[:12]


# ---------------------------------------------------------------------------
# Output: the plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SolverPlan:
    """One resolved geometry + route for a (workload, topology) pair.

    ``solver`` is what ``local_solver="auto"`` should resolve to
    ("pallas" | "xla"); ``route`` the kernel variant
    ("pallas-replicated" | "pallas-sharded" | "xla"); ``origin`` how
    the plan was produced ("static" | "search" | "probe" | "cache").
    ``score`` is the analytic bytes-per-effective-epoch (lower is
    better; comparable only within one workload x topology).
    ``reason`` carries the misfit string for "xla" routes and the
    decision rationale otherwise; ``reason_code`` its stable
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
    origin: str = "static"
    score: float = 0.0
    probe_s: float = -1.0         # timed probe epoch seconds (-1 = none)
    version: int = PLAN_VERSION

    def to_json(self) -> dict:
        """JSON-safe dict (the on-disk + BENCH-json record shape)."""
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, doc: dict) -> "SolverPlan":
        """Inverse of `to_json`; unknown keys are ignored so the schema
        can grow without breaking older readers."""
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in doc.items() if k in names})


# ---------------------------------------------------------------------------
# Mode (the $REPRO_PLAN escape hatch)
# ---------------------------------------------------------------------------

_MODES = ("on", "off", "search", "probe")


def plan_mode() -> str:
    """Parse ``$REPRO_PLAN`` -> "on" | "off" | "search" | "probe".

    The ONE parser of the env hatch (mirrors
    `engine._resolve_auto` for $REPRO_LOCAL_SOLVER).  Unset/empty means
    "on"; anything unrecognized raises so typos cannot silently change
    solver behavior.
    """
    env = os.environ.get("REPRO_PLAN", "").strip().lower()
    if not env:
        return "on"
    if env not in _MODES:
        raise ValueError(
            f"$REPRO_PLAN={env!r}: must be one of {', '.join(_MODES)}")
    return env


# ---------------------------------------------------------------------------
# Feasibility + routing (delegates to the kernels' own predicates)
# ---------------------------------------------------------------------------


def _sparse_route(nnz: int, d: int, bucket: int,
                  model_lanes: int) -> tuple[str, Optional[str]]:
    """Route a sparse geometry through `ops.sparse_solver_plan` with
    n_local=bucket (Session/cache padding guarantees divisibility, so
    only the alignment/VMEM misfits matter at plan time)."""
    from repro.kernels import ops as kops
    return kops.sparse_solver_plan(bucket, nnz, d, bucket,
                                   model_lanes=model_lanes)


def _dense_route(d: int, bucket: int) -> tuple[str, Optional[str]]:
    from repro.kernels import ops as kops
    why = kops.dense_kernel_misfit(d, bucket, bucket)
    return ("xla", why) if why else ("pallas-replicated", None)


def route_sparse(n_local: int, nnz: int, d: int, bucket: int, *,
                 model_lanes: int = 1) -> tuple[str, Optional[str]]:
    """Trace-time sparse route for the engine's backend-picked "auto".

    A pure delegation to `kernels.ops.sparse_solver_plan`,
    deliberately: the planner ranks among feasible geometries but can
    NEVER loosen the kernels' own predicates, so the engine's
    never-regress fallback verdicts are byte-identical with the
    planner on, off, or broken — $REPRO_PLAN does not (and must not)
    change what this function returns.
    """
    from repro.kernels import ops as kops
    return kops.sparse_solver_plan(n_local, nnz, d, bucket,
                                   model_lanes=model_lanes)


def route_dense(d: int, n_local: int, bucket: int) -> Optional[str]:
    """Trace-time dense misfit for the engine's backend-picked "auto"
    (reason string or None) — see `route_sparse` for why this is a
    delegation, not a policy point."""
    from repro.kernels import ops as kops
    return kops.dense_kernel_misfit(d, n_local, bucket)


def _plan_feasible(sig: WorkloadSignature, topo: Topology,
                   plan: SolverPlan) -> bool:
    """The never-regress pre-check: a pallas plan must still pass the
    kernels' misfit predicates; an xla plan is always safe."""
    if plan.solver != "pallas":
        return True
    nnz = _effective_nnz(sig, plan.nnz_multiple)
    if sig.sparse:
        lanes = topo.model_lanes if plan.feature_shard else 1
        route, _ = _sparse_route(nnz, sig.d, plan.bucket, lanes)
        return route == plan.route
    route, _ = _dense_route(sig.d, plan.bucket)
    return route == "pallas-replicated"


def _effective_nnz(sig: WorkloadSignature, nnz_multiple: int) -> int:
    if not sig.sparse:
        return 0
    if nnz_multiple:
        return _round_up(max(sig.nnz, 1), nnz_multiple)
    return sig.nnz


def feature_shard_default(sig: WorkloadSignature,
                          topo: Optional[Topology] = None) -> bool:
    """The layout boundary `launch/glm.py scale_for_dataset` used to
    hardcode: shard features over 'model' exactly when the replicated
    shared vector cannot fit the sparse kernel's resident-v VMEM
    budget (sparse), or when d is TP-wide (dense, d >= 512).

    Owned by the planner so the boundary is written ONCE; with
    ``$REPRO_PLAN=off`` the same expressions run inline (they ARE the
    static rule — this function never disagrees with it).
    """
    if topo is None:
        topo = Topology(backend="tpu")
    if sig.sparse:
        from repro.kernels.sdca_sparse_bucket import v_bytes
        return v_bytes(sig.d) > topo.v_budget()
    return sig.d >= 512


# ---------------------------------------------------------------------------
# Static resolution (today's rules, as one function)
# ---------------------------------------------------------------------------


def static_plan(sig: WorkloadSignature, topo: Topology, *,
                bucket: Optional[int] = None,
                chunks: Optional[int] = None,
                nnz_multiple: Optional[int] = None) -> SolverPlan:
    """Today's fixed-default resolution, expressed as a `SolverPlan`.

    This is both the ``$REPRO_PLAN=off`` behavior and the warn-and-safe
    fallback for every planner failure: bucket from the caller (else
    `bucketing.choose_bucket_size`), chunks from the caller (else 1),
    feature_shard from `feature_shard_default`, solver route from the
    kernels' own predicates on the resulting geometry.
    """
    from repro.core.bucketing import choose_bucket_size
    B = bucket if bucket else choose_bucket_size(sig.n, sig.d)
    C = chunks if chunks else 1
    zmult = nnz_multiple or 0
    shard = feature_shard_default(sig, topo)
    plan = _routed_plan(sig, topo, B, C, zmult, shard, origin="static")
    return plan


def _routed_plan(sig: WorkloadSignature, topo: Topology, bucket: int,
                 chunks: int, nnz_multiple: int, feature_shard: bool,
                 origin: str) -> SolverPlan:
    """Attach the kernels' route verdict + analytic score to a
    candidate geometry."""
    nnz = _effective_nnz(sig, nnz_multiple)
    if sig.sparse:
        lanes = topo.model_lanes if feature_shard else 1
        route, reason = _sparse_route(nnz, sig.d, bucket, lanes)
    else:
        route, reason = _dense_route(sig.d, bucket)
    solver = "xla" if route == "xla" else "pallas"
    if topo.backend != "tpu":
        # backend-picked "auto" resolves to xla off-TPU; the plan
        # records what WOULD run on TPU in `route` but scores/solves
        # for the machine at hand
        solver = "xla"
    plan = SolverPlan(
        solver=solver, route=route, bucket=bucket, chunks=chunks,
        nnz_multiple=nnz_multiple, feature_shard=feature_shard,
        reason=str(reason or "fits"),
        reason_code=getattr(reason, "code", ""), origin=origin)
    return dataclasses.replace(plan, score=plan_cost(sig, topo, plan))


# ---------------------------------------------------------------------------
# The search: candidates -> analytic score -> (optional) probe epochs
# ---------------------------------------------------------------------------


def candidate_plans(sig: WorkloadSignature, topo: Topology, *,
                    bucket: Optional[int] = None,
                    chunks: Optional[int] = None,
                    nnz_multiple: Optional[int] = None
                    ) -> list[SolverPlan]:
    """Enumerate the search space, respecting caller-fixed knobs.

    Dimensions: bucket (sublane multiples up to the dense cap), chunks
    (sync intervals that divide the bucket count), nnz_multiple (0 =
    keep the raw row width, 8 = pad to the sparse kernels' lane
    alignment — only offered when the width is unaligned), and
    replicated vs feature-sharded layout (sharded only when the
    topology HAS model lanes).  Every candidate carries the kernels'
    route verdict; infeasible-for-pallas candidates are kept with
    route="xla" (the scan is always a legal geometry).
    """
    buckets = (bucket,) if bucket else BUCKET_CANDIDATES
    chunk_opts = (chunks,) if chunks else CHUNK_CANDIDATES
    if nnz_multiple is not None:
        zmults: tuple[int, ...] = (nnz_multiple,)
    elif sig.sparse and sig.nnz % 8:
        zmults = (0, 8)
    else:
        zmults = (0,)
    layouts = [False]
    if topo.model_lanes > 1 or feature_shard_default(sig, topo):
        layouts.append(True)
    out = []
    for B in buckets:
        for C in chunk_opts:
            nb = max(sig.n // max(B, 1), 1)
            if nb % C:
                continue
            for z in zmults:
                for shard in layouts:
                    out.append(_routed_plan(sig, topo, B, C, z, shard,
                                            origin="search"))
    return out


def streamed_transfer_bytes(sig: WorkloadSignature, topo: Topology,
                            plan: SolverPlan) -> float:
    """Modeled host->device bytes per device per streamed epoch.

    The ONE h2d byte model (DESIGN.md S16): `plan_cost`'s streamed
    score term, `launch/glm.py glm_analytic(streamed=True)`, and the
    fig4/roofline benchmark figures all report this quantity, so the
    planner and the bench artifacts can never disagree about what
    "ingest bytes" means.  Mirrors what `engine.MeshChunkFeed`
    actually ships:

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
    n_loc = max(sig.n // max(topo.workers, 1), 1)
    y_bytes = n_loc * 4
    if sig.sparse:
        nnz = max(_effective_nnz(sig, plan.nnz_multiple), 1)
        if plan.feature_shard and topo.model_lanes > 1:
            mult = plan.nnz_multiple or 8
            w = min(_round_up(-(-nnz // topo.model_lanes), mult), nnz)
            return float(n_loc * w * 12 + y_bytes)
        return float(n_loc * nnz * 8 + y_bytes)
    d_loc = sig.d
    if plan.feature_shard and topo.model_lanes > 1:
        d_loc = -(-sig.d // topo.model_lanes)
    return float(n_loc * d_loc * sig.dtype_bytes + y_bytes)


def plan_cost(sig: WorkloadSignature, topo: Topology,
              plan: SolverPlan) -> float:
    """Analytic score: modeled HBM bytes per EFFECTIVE epoch, per device.

    Per-epoch traffic mirrors the fig6 throughput models (DESIGN.md
    S11/S12): every route streams the data once; the XLA scan also
    pays an HBM gather + read-modify-write scatter against v per
    coordinate; the replicated kernel pays v only at chunk syncs; the
    sharded kernel round-trips its d/M slice per bucket and receives
    the all-gathered (M, B, nnz) working set.  The result is then
    multiplied by a mild convergence factor penalizing coarse shuffles
    (large B) and stale replicas (few chunks with many workers) — the
    SySCD speed/convergence trade-off.  A ranking device, not a
    simulator: probe epochs (`probe_plans`) are the ground truth.
    """
    n_loc = max(sig.n // topo.workers, 1)
    B, C = plan.bucket, max(plan.chunks, 1)
    nnz = _effective_nnz(sig, plan.nnz_multiple)
    if sig.sparse:
        data = n_loc * nnz * (4 + sig.dtype_bytes)
        sync = C * sig.d * sig.dtype_bytes * 2
        if plan.route == "pallas-replicated":
            traffic = data + sync
        elif plan.route == "pallas-sharded":
            from repro.kernels.ops import sparse_slice_width
            M = max(topo.model_lanes, 1)
            d_loc = sparse_slice_width(sig.d, M)
            nb = max(n_loc // B, 1)
            traffic = (data + nb * d_loc * sig.dtype_bytes * 2
                       + nb * M * B * nnz * sig.dtype_bytes + sync)
        else:
            traffic = data + n_loc * nnz * sig.dtype_bytes * 3 + sync
    else:
        d_loc = sig.d
        data = n_loc * d_loc * sig.dtype_bytes
        sync = C * d_loc * sig.dtype_bytes * 2
        if plan.route == "pallas-replicated" and plan.solver == "pallas":
            traffic = data + sync
        else:
            # the scan re-touches v per bucket (Gram + margin carry)
            traffic = data + max(n_loc // B, 1) * d_loc \
                * sig.dtype_bytes * 2 + sync
    if sig.streamed:
        # out-of-core: every epoch re-ships the chunks over the host
        # link — score those bytes at their HBM-equivalent weight so a
        # streamed plan's geometry sees the ~50x slower ingest lane
        traffic += streamed_transfer_bytes(sig, topo, plan) \
            * (_HBM_BW / H2D_BW)
    conv = 1.0 + CONV_BUCKET_COST * max(math.log2(max(B, 8) / 8), 0.0)
    W = topo.workers
    if W > 1:
        conv *= 1.0 + CONV_SYNC_COST * (W - 1) / W / C
    return float(traffic) * conv


def search_plans(sig: WorkloadSignature, topo: Topology, *,
                 bucket: Optional[int] = None,
                 chunks: Optional[int] = None,
                 nnz_multiple: Optional[int] = None,
                 top_k: int = 3) -> list[SolverPlan]:
    """Ranked (best-first) feasible plans under the analytic model.

    Ties break toward the static layout (`feature_shard_default`) and
    then the smaller bucket: when the model cannot tell two candidates
    apart, the planner must not drift from today's resolution — the
    never-regress contract applies to score ties too.
    """
    cands = candidate_plans(sig, topo, bucket=bucket, chunks=chunks,
                            nnz_multiple=nnz_multiple)
    cands = [c for c in cands if _plan_feasible(sig, topo, c)]
    shard0 = feature_shard_default(sig, topo)
    cands.sort(key=lambda p: (p.score, p.feature_shard != shard0,
                              p.bucket, p.chunks, p.nnz_multiple))
    return cands[:max(top_k, 1)]


def probe_plans(cands: list[SolverPlan],
                probe_fn: Callable[[SolverPlan], float]) -> SolverPlan:
    """Refine a ranked candidate list with timed probe epochs.

    ``probe_fn(plan) -> seconds`` runs a few real epochs of the
    workload under the candidate geometry (the fig6 planner arm builds
    one from a Session; operators can pass their own).  The fastest
    measured candidate wins; a probe that raises disqualifies its
    candidate rather than the whole search.  Returns the winner with
    ``origin="probe"`` and its measured seconds in ``probe_s``.
    """
    best: Optional[SolverPlan] = None
    for cand in cands:
        try:
            dt = float(probe_fn(cand))
        # audit: except-ok a failed probe is warned about and skipped
        except Exception as e:            # pragma: no cover - probe-dep
            warnings.warn(f"plan probe failed for bucket={cand.bucket} "
                          f"chunks={cand.chunks}: {e}", stacklevel=2)
            continue
        timed = dataclasses.replace(cand, probe_s=dt, origin="probe")
        if best is None or dt < best.probe_s:
            best = timed
    if best is None:
        raise RuntimeError("every probe candidate failed")
    return best


# ---------------------------------------------------------------------------
# Disk cache (alongside the tile cache)
# ---------------------------------------------------------------------------


def plan_cache_dir(cache_dir=None) -> pathlib.Path:
    """Where plans live: ``<tile-cache root>/plans`` (so one
    $REPRO_CACHE_DIR move relocates both)."""
    from repro.data.registry import cache_root
    return cache_root(cache_dir) / "plans"


def _plan_path(sig: WorkloadSignature, topo: Topology,
               cache_dir=None) -> pathlib.Path:
    name = f"{sig.name}-" if sig.name else ""
    return plan_cache_dir(cache_dir) / (
        f"{name}{sig.fingerprint()}-{topo.fingerprint()}"
        f"-v{PLAN_VERSION}.json")


def store_plan(sig: WorkloadSignature, topo: Topology, plan: SolverPlan,
               cache_dir=None) -> pathlib.Path:
    """Persist a plan (atomic rename, sorted keys — byte-stable like
    the tile cache's meta.json)."""
    path = _plan_path(sig, topo, cache_dir)
    path.parent.mkdir(parents=True, exist_ok=True)
    doc = {"magic": "repro-solver-plan", "version": PLAN_VERSION,
           "signature": dataclasses.asdict(sig),
           "topology": dataclasses.asdict(topo),
           "plan": plan.to_json()}
    tmp = path.with_suffix(".tmp")
    tmp.write_text(json.dumps(doc, sort_keys=True, indent=1) + "\n")
    os.replace(tmp, path)
    return path


def load_cached_plan(sig: WorkloadSignature, topo: Topology,
                     cache_dir=None) -> Optional[SolverPlan]:
    """Load + validate a cached plan; None on miss/skew/corruption.

    Validation is the never-regress gate: version must match
    PLAN_VERSION (the filename key AND the stored field — a bump
    invalidates even a hand-renamed file) and the plan must still pass
    the kernels' misfit predicates (budgets can tighten between
    versions).
    """
    path = _plan_path(sig, topo, cache_dir)
    try:
        if not path.exists():
            return None
        doc = json.loads(path.read_text())
        if (doc.get("magic") != "repro-solver-plan"
                or doc.get("version") != PLAN_VERSION):
            return None
        plan = SolverPlan.from_json(doc["plan"])
        if plan.version != PLAN_VERSION:
            return None
        if not _plan_feasible(sig, topo, plan):
            return None
        return dataclasses.replace(plan, origin="cache")
    # audit: except-ok unreadable/stale cache entry -> plan from scratch
    except Exception:
        return None


# ---------------------------------------------------------------------------
# The front door
# ---------------------------------------------------------------------------


def resolve_plan(sig: WorkloadSignature, topo: Optional[Topology] = None,
                 *, bucket: Optional[int] = None,
                 chunks: Optional[int] = None,
                 nnz_multiple: Optional[int] = None,
                 cache_dir=None,
                 probe_fn: Optional[Callable[[SolverPlan], float]] = None,
                 use_cache: bool = True) -> SolverPlan:
    """Workload + topology -> `SolverPlan`, honoring ``$REPRO_PLAN``.

    Caller-fixed knobs (bucket/chunks/nnz_multiple given explicitly)
    are never overridden — the planner only decides what was left
    open.  Resolution ladder:

      off    -> `static_plan` (today's rules), nothing cached;
      cache  -> a stored plan for this (fingerprint, topology,
                version) that still passes the misfit pre-checks;
      on     -> static geometry if feasible, else the best feasible
                search candidate (the "repair" case);
      search -> best candidate under the analytic cost model;
      probe  -> search, then timed probe epochs over the top
                candidates when ``probe_fn`` is given.

    Any exception inside the planner degrades warn-and-safe to
    `static_plan` — a broken plan cache can never take down training.
    """
    if topo is None:
        topo = Topology.detect()
    mode = plan_mode()
    fixed = dict(bucket=bucket, chunks=chunks, nnz_multiple=nnz_multiple)
    if mode == "off":
        return static_plan(sig, topo, **fixed)
    try:
        if use_cache:
            cached = load_cached_plan(sig, topo, cache_dir)
            if cached is not None and _respects_fixed(cached, fixed):
                return cached
        static = static_plan(sig, topo, **fixed)
        if mode == "on":
            plan = static if _plan_feasible(sig, topo, static) else None
            if plan is None:
                ranked = search_plans(sig, topo, **fixed)
                plan = ranked[0] if ranked else static
        else:
            ranked = search_plans(sig, topo, **fixed)
            if not ranked:
                plan = static
            elif mode == "probe" and probe_fn is not None:
                plan = probe_plans(ranked, probe_fn)
            else:
                plan = ranked[0]
        if not _plan_feasible(sig, topo, plan):
            warnings.warn(
                "planner produced an infeasible plan "
                f"(bucket={plan.bucket}, route={plan.route}); using the "
                "static resolution instead", stacklevel=2)
            return static
        if use_cache and plan.origin != "static":
            store_plan(sig, topo, plan, cache_dir)
        return plan
    # audit: except-ok planner failure degrades to the static plan + warn
    except Exception as e:
        warnings.warn(
            f"solver planner failed ({type(e).__name__}: {e}); falling "
            f"back to static resolution ($REPRO_PLAN=off silences this)",
            stacklevel=2)
        return static_plan(sig, topo, **fixed)


def _respects_fixed(plan: SolverPlan, fixed: dict) -> bool:
    """A cached plan only applies when it agrees with every knob the
    caller pinned explicitly."""
    if fixed["bucket"] is not None and plan.bucket != fixed["bucket"]:
        return False
    if fixed["chunks"] is not None and plan.chunks != fixed["chunks"]:
        return False
    if (fixed["nnz_multiple"] is not None
            and plan.nnz_multiple != fixed["nnz_multiple"]):
        return False
    return True
