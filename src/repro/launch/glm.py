"""Distributed GLM training: the solver engine as a 3-axis SPMD program.

shard_map over ("pod","data","model") implements the paper's hierarchy
with real collectives (DESIGN.md S2).  The epoch program itself —
re-deal -> chunked local sub-epoch -> sync -> pod reduce — lives in
`repro.core.engine` and is shared verbatim with the vmap simulator;
this module only binds it to a mesh:

  * static partition of examples across pods — data never crosses the
    pod interconnect; only the d-sized v delta does, once per epoch
    (optionally int8 error-feedback compressed: 4x fewer wire bytes);
  * DYNAMIC partition within a pod — every epoch each lane shuffles its
    buckets locally, splits them into K groups and exchanges via ONE
    balanced all-to-all over 'data' (`MeshCollectives.redeal`); a ring
    rotation of whole blocks was tried first and REFUTED — see
    core/partition.py + EXPERIMENTS.md;
  * feature sharding over 'model' (TP) for wide datasets — dense: v
    rows are sharded and per-bucket Gram/margin partial sums are
    psum'd; sparse: each model lane owns a contiguous d/M slice of v
    (VMEM-resident in the sharded Pallas kernel, DESIGN.md S12), one
    working-set exchange per bucket, and the model axis joins the dv
    SYNC axes so the ordered reduction reassembles the slices — in
    both cases ONE model-axis collective amortized over B coordinates
    (the bucket optimization's TP payoff);
  * v replicas sync over 'data' once per chunk, so compute and the
    data-axis reduction interleave across chunks.

Workers = pods x data-lanes (x model-lanes too when features are
replicated — narrow datasets use the whole mesh as example-parallel
workers).  sigma' = #workers (CoCoA+ additive aggregation).

`GLMScale.local_solver="pallas"` routes each worker's sub-epoch through
the Pallas bucket kernels — dense (kernels/sdca_bucket.py) AND sparse
(kernels/sdca_sparse_bucket.py, VMEM-resident shared vector over CSR
tiles) — instead of the XLA scans; "auto" picks pallas on TPU backends
(DESIGN.md S11).  It is the same `LocalSolver` seam the simulator uses.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import engine
from repro.core.config import AlgoConfig, DeploymentConfig, EngineConfig
from repro.core.objectives import LOGISTIC, NEWTON_STEPS, Objective

# The shard_map wrapper (check_vma off — see the note in
# core/engine.py) lives in the engine with the streamed mesh path;
# re-exported here for existing importers.
from repro.core.engine import shard_map  # noqa: F401  (re-export)


@dataclasses.dataclass(frozen=True)
class GLMScale:
    """One deployment-scale GLM workload (paper dataset, full size)."""
    name: str
    kind: str                 # dense | sparse
    n: int
    d: int
    nnz: int = 0              # sparse only (padded)
    bucket: int = 16
    chunks: int = 4           # v syncs per epoch over 'data'
    feature_shard: bool = False   # wide data: shard d over 'model'
    #   (dense: TP v rows + psum; sparse: sharded-v solver slices)
    lam: float = 1e-3
    compress_pod: bool = True     # int8 EF for the cross-pod reduce
    compress_sync: bool = False   # int8 two-phase data-axis dv reduction
    redeal_frac: float = 1.0      # bucket fraction re-dealt per epoch
    local_solver: str = "auto"    # auto|xla|pallas (engine LocalSolver)
    deterministic: bool = False   # ordered gather-sums (bit-stable)
    # the mesh backend supports the two PHYSICAL partition modes:
    # "alltoall" (the TPU-native dynamic re-deal) and "static"
    partition: str = "alltoall"
    aggregation: str = "adding"   # CoCoA(+) sigma' rule
    seed: int = 0                 # schedule/re-deal PRNG root

    def engine_config(self, mesh=None) -> EngineConfig:
        """The layered engine view of this workload's solver knobs."""
        dep = DeploymentConfig(
            pods=mesh.shape.get("pod", 1) if mesh is not None else 1,
            lanes=(_worker_count(mesh, self)
                   // mesh.shape.get("pod", 1)) if mesh is not None else 1,
            feature_shard=self.feature_shard,
            compress_pod=self.compress_pod,
            deterministic=self.deterministic)
        return EngineConfig(
            algo=AlgoConfig(bucket=self.bucket, chunks=self.chunks,
                            aggregation=self.aggregation,
                            partition=self.partition,
                            redeal_frac=self.redeal_frac,
                            local_solver=self.local_solver,
                            compress_sync=self.compress_sync,
                            seed=self.seed),
            deployment=dep)


GLM_CONFIGS = {
    # criteo-kaggle: 45M examples, 1M features, ~39 nnz (padded to 40)
    "glm-criteo": GLMScale("glm-criteo", "sparse", n=45_088_768,
                           d=1_048_576, nnz=40, bucket=16, chunks=4),
    # HIGGS: 11M examples, 28 dense features — narrow: replicate features,
    # use every chip as an example-parallel worker
    "glm-higgs": GLMScale("glm-higgs", "dense", n=11_010_048, d=28,
                          bucket=8, chunks=4, feature_shard=False),
    # epsilon: 400k examples, 2000 dense features — wide: TP over 'model'
    "glm-epsilon": GLMScale("glm-epsilon", "dense", n=409_600, d=2_000,
                            bucket=16, chunks=8, feature_shard=True),
    # webspam-trigram: 350k examples, 16.6M features, ~3727 nnz — d is
    # ~8x over the replicated-v VMEM budget, so this is THE
    # feature-sharded sparse workload: model lanes each hold a d/M
    # slice of v and run the sharded bucket kernel (DESIGN.md S12)
    "glm-webspam": GLMScale("glm-webspam", "sparse", n=360_448,
                            d=16_609_280, nnz=3_728, bucket=16,
                            chunks=4, feature_shard=True),
    # beyond-paper optimized variant (SPerf glm iteration): int8
    # two-phase chunk reductions + 25% partial re-deal
    "glm-criteo-opt": GLMScale("glm-criteo-opt", "sparse", n=45_088_768,
                               d=1_048_576, nnz=40, bucket=16, chunks=4,
                               compress_sync=True, redeal_frac=0.25),
}


def scale_for_dataset(name: str, **overrides) -> GLMScale:
    """Registry dataset -> a deployment-scale `GLMScale`.

    Sizes come from the dataset registry's REAL shapes (not the offline
    sub-samples): n is padded to a 32k multiple and d/nnz to mesh- and
    tile-friendly multiples, mirroring how the hand-written GLM_CONFIGS
    entries were derived from the paper's tables.  The data layout —
    and, under ``$REPRO_PLAN=search|probe``, the bucket/chunk geometry
    — resolves through the system-aware planner (`core.planner`,
    DESIGN.md S13): wide dense datasets (d >= 512) feature-shard over
    'model'; sparse datasets do exactly when the replicated shared
    vector cannot fit the kernel's VMEM budget (webspam-scale d) — the
    same boundary `kernels.ops.sparse_solver_plan` dispatches on, now
    written once in `planner.feature_shard_default`.  Explicit
    overrides always win, and any planner failure degrades
    warn-and-safe to that static layout rule.
    """
    from repro.core import planner
    from repro.data.registry import get_spec

    spec = get_spec(name)
    n = -(-spec.full_n // 32_768) * 32_768
    d = -(-spec.full_d // 4_096) * 4_096 if spec.full_d >= 4_096 \
        else spec.full_d
    kw = dict(name=f"glm-{name}", kind=spec.kind, n=n, d=d,
              lam=spec.lam)
    sparse = spec.kind == "sparse"
    if sparse:
        kw["nnz"] = -(-spec.nnz // 8) * 8
    sig = planner.WorkloadSignature(n=n, d=d, nnz=kw.get("nnz", 0),
                                    sparse=sparse, name=name)
    searching = planner.plan_mode() in ("search", "probe")
    plan = planner.resolve_plan(
        sig, planner.Topology.detect(),
        bucket=overrides.get("bucket", None if searching else 16),
        chunks=overrides.get("chunks", None if searching else 4))
    kw["feature_shard"] = plan.feature_shard
    if searching:
        kw["bucket"], kw["chunks"] = plan.bucket, plan.chunks
    kw.update(overrides)
    return GLMScale(**kw)


def scale_for_estimator(est, **overrides) -> GLMScale:
    """A FITTED `repro.api` estimator (or bare `Session`) -> `GLMScale`.

    The deployment-scale view is derived from the estimator's own
    solver state: data dims from its session, algorithm knobs from its
    `EngineConfig` — so the mesh program it lowers to runs the *same*
    epoch the estimator ran in the simulator."""
    ses = getattr(est, "session_", est)
    if not hasattr(ses, "spec") or not hasattr(ses, "n"):
        raise ValueError(
            "estimator_epoch needs a fitted estimator (or a Session): "
            "the mesh program is sized from its data and config")
    algo, dep = ses.spec.algo, ses.spec.deployment
    kind = "sparse" if ses.sparse else "dense"
    kw = dict(name=f"glm-{type(est).__name__.lower()}", kind=kind,
              n=ses.n, d=ses.d, bucket=ses.bplan.bucket,
              chunks=algo.chunks, lam=ses.lam,
              compress_pod=dep.compress_pod,
              compress_sync=algo.compress_sync,
              redeal_frac=algo.redeal_frac,
              local_solver=algo.local_solver,
              deterministic=dep.deterministic,
              # the mesh has two physical partition modes; every sim
              # re-dealing scheme maps onto the all-to-all re-deal
              partition=("static" if algo.partition == "static"
                         else "alltoall"),
              aggregation=algo.aggregation, seed=algo.seed)
    if kind == "sparse":
        if ses.cache is not None:
            kw["nnz"] = ses.cache.meta.nnz
        elif hasattr(ses, "idx"):
            kw["nnz"] = int(ses.idx.shape[1])
        elif "nnz" not in overrides:
            raise ValueError("sparse feed-backed session: pass nnz=...")
    else:
        kw["feature_shard"] = dep.feature_shard
    kw.update(overrides)
    return GLMScale(**kw)


def estimator_epoch(est, mesh, **overrides):
    """Lower an `repro.api` estimator onto a device mesh, as a program
    over global arrays the caller builds and feeds.

    The normal path is `Session` itself: with `lanes` workers and as
    many devices, or with ``mesh=``, it places its rows on the mesh and
    runs this same program every epoch (`DeploymentConfig.lanes_on`).
    Returns ``(epoch_fn, scale)``: `epoch_fn` is the shard_map'd epoch
    program over global arrays (same signature as `make_dense_epoch` /
    `make_sparse_epoch` products; jit/donate and feed it
    `glm_input_specs(scale, mesh)`-shaped arrays), `scale` the derived
    `GLMScale`.  The estimator's algorithm knobs (bucket, chunks,
    aggregation, seed, compression, determinism) carry over verbatim;
    its partition scheme maps onto the mesh's physical modes ("static"
    stays static, every re-dealing scheme becomes the TPU-native
    all-to-all re-deal).  With `deterministic=True` and a
    static/alltoall-partition estimator, the mesh program is
    bitwise-identical to the engine's stacked-sim epochs on P pods x K
    data-lane layouts (the S2 sim<->mesh contract); other sim schedule
    modes are convergence-equivalent, not bitwise.
    """
    from repro.core.objectives import get_objective

    scale = scale_for_estimator(est, **overrides)
    objective = getattr(est, "_objective", None)
    obj = get_objective(objective) if objective else getattr(
        getattr(est, "session_", est), "obj", LOGISTIC)
    make = make_sparse_epoch if scale.kind == "sparse" else make_dense_epoch
    return make(scale, mesh, obj=obj), scale


def _axes(mesh, scale: GLMScale):
    """-> (example_axes, sync_axes, has_pod, model_is_tp).

    feature_shard picks the model axis's ROLE.  Dense TP shards the v
    rows themselves (P("model") specs, tp=True).  Sparse feature
    sharding keeps v replicated at the XLA level, but each model lane's
    SOLVER only writes its contiguous d/M slice (sharded kernel /
    masked scan), so 'model' leaves the example axes and joins the
    SYNC axes: the ordered dv reduction reassembles the disjoint
    slices.  Without feature_shard the model axis is just more
    example-parallel workers.
    """
    names = mesh.axis_names
    has_pod = "pod" in names
    if scale.feature_shard:
        ex = tuple(a for a in ("pod", "data") if a in names)
        if scale.kind == "dense":
            sync = ("data",)
            tp = True
        else:
            sync = tuple(a for a in ("data", "model") if a in names)
            tp = False
    else:
        ex = tuple(a for a in ("pod", "data", "model") if a in names)
        sync = tuple(a for a in ("data", "model") if a in names)
        tp = False
    return ex, sync, has_pod, tp


def _worker_count(mesh, scale: GLMScale) -> int:
    ex, _, _, _ = _axes(mesh, scale)
    n = 1
    for a in ex:
        n *= mesh.shape[a]
    return n


def _collectives(mesh, scale: GLMScale) -> engine.MeshCollectives:
    ex_axes, sync_axes, has_pod, _ = _axes(mesh, scale)
    sizes = {a: mesh.shape.get(a, 1) for a in ("pod", "data", "model")}
    return engine.MeshCollectives(
        lane_axes=tuple(a for a in ex_axes if a != "pod"),
        sync_axes=sync_axes, axis_sizes=sizes,
        pod_axis="pod" if has_pod else None, redeal_axis="data",
        deterministic=scale.deterministic,
        compress_pod=scale.compress_pod)


def _specs(scale: GLMScale, mesh):
    """-> (block specs, example spec, v spec): how a resident epoch's
    rows, its per-example vectors (y, alpha, row ids) and v lie on the
    mesh."""
    ex_axes, _, _, tp = _axes(mesh, scale)
    if scale.kind == "sparse":
        block = (P(ex_axes, None),) * 2
    else:
        block = (P("model" if tp else None, ex_axes),)
    return block, P(ex_axes), P("model") if tp else P(None)


def _epoch_specs(scale: GLMScale, mesh, row_ids: bool):
    """shard_map (in_specs, out_specs) of an epoch over (*block, y,
    [rid,] alpha, v, epoch) -> (*block, y, [rid,] alpha, v)."""
    block, e_spec, v_spec = _specs(scale, mesh)
    out = block + (e_spec,) * (3 if row_ids else 2) + (v_spec,)
    return out + (P(),), out


def resident_shardings(scale: GLMScale, mesh):
    """-> (block shardings, example sharding, v sharding): where
    `Session` places resident rows for the epoch programs below, so
    each epoch's outputs lie exactly as its inputs did."""
    block, e_spec, v_spec = _specs(scale, mesh)
    return (tuple(NamedSharding(mesh, b) for b in block),
            NamedSharding(mesh, e_spec), NamedSharding(mesh, v_spec))


def make_dense_epoch(scale: GLMScale, mesh, obj: Objective = LOGISTIC, *,
                     row_ids: bool = False, damp: float = 1.0):
    """-> jit-ready epoch fn over global arrays (X, y, alpha, v, epoch).

    ``row_ids=True`` adds each example's row id after y, re-dealt with
    the example and returned in the same place (what `Session` reads
    alpha back in row order by); ``damp`` is the health guard's
    dv_scale multiplier."""
    _, _, _, tp = _axes(mesh, scale)
    W = _worker_count(mesh, scale)
    spec = scale.engine_config(mesh)
    coll = _collectives(mesh, scale)
    model_axis = "model" if tp else None

    def epoch_fn(X, y, *rest):
        # X: (d_loc, n_local) f32; y/a: (n_local,); v: (d_loc,)
        *rid, a, v, epoch = rest
        blk, y, a, v = engine.sharded_epoch(
            obj, spec, coll, engine.DenseBlock(X, *rid), y, a, v, epoch,
            lam=scale.lam, n_total=scale.n, workers=W,
            model_axis=model_axis, dv_scale_mul=damp)
        return (blk.X, y) + ((blk.rid,) if row_ids else ()) + (a, v)

    in_specs, out_specs = _epoch_specs(scale, mesh, row_ids)
    return shard_map(epoch_fn, mesh, in_specs=in_specs,
                     out_specs=out_specs)


def make_sparse_epoch(scale: GLMScale, mesh, obj: Objective = LOGISTIC,
                      *, interpret: bool | None = None,
                      row_ids: bool = False, damp: float = 1.0):
    """-> jit-ready epoch fn over global arrays (idx, val, y, alpha, v,
    epoch); ``row_ids`` and ``damp`` as in `make_dense_epoch`.
    `interpret` forces the Pallas kernels' interpret mode (tests drive
    TPU-targeted solver selection on CPU hosts with it); None = backend
    default."""
    W = _worker_count(mesh, scale)
    spec = scale.engine_config(mesh)
    coll = _collectives(mesh, scale)
    sparse_tp = scale.feature_shard and "model" in mesh.axis_names
    model_axis = "model" if sparse_tp else None
    model_lanes = mesh.shape["model"] if sparse_tp else None

    def epoch_fn(idx, val, y, *rest):
        # idx/val: (n_local, nnz); v: (d,) replicated at the XLA level
        # even when feature-sharded — each lane's solver writes only
        # its own d/M slice and the model-axis sync reassembles them
        *rid, a, v, epoch = rest
        blk, y, a, v = engine.sharded_epoch(
            obj, spec, coll, engine.SparseBlock(idx, val, *rid), y, a, v,
            epoch, lam=scale.lam, n_total=scale.n, workers=W,
            model_axis=model_axis, model_lanes=model_lanes,
            interpret=interpret, dv_scale_mul=damp)
        return (blk.idx, blk.val, y) + ((blk.rid,) if row_ids else ()) \
            + (a, v)

    in_specs, out_specs = _epoch_specs(scale, mesh, row_ids)
    return shard_map(epoch_fn, mesh, in_specs=in_specs,
                     out_specs=out_specs)


def redeal_bytes(scale: GLMScale, mesh, *, row_ids: bool = False) -> int:
    """Bytes an epoch's all-to-all re-deal sends between devices, summed
    over the mesh, from the shapes: each device splits its exchanged
    buckets over the `data` lanes and keeps one share; every array of an
    example moves (its row, y, alpha, and the row id where carried)."""
    K = mesh.shape.get("data", 1)
    if scale.partition == "static" or K <= 1 or scale.redeal_frac <= 0:
        return 0
    _, _, _, tp = _axes(mesh, scale)
    nb_local = scale.n // _worker_count(mesh, scale) // scale.bucket
    exch = max(int(nb_local * scale.redeal_frac) // K * K, K)
    if scale.kind == "sparse":
        row = scale.nnz * 8
    else:
        row = (scale.d // mesh.shape["model"] if tp else scale.d) * 4
    row += 4 * (3 if row_ids else 2)
    return mesh.size * exch * scale.bucket * row * (K - 1) // K


def glm_input_specs(scale: GLMScale, mesh):
    block, e_spec, v_spec = _specs(scale, mesh)

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    if scale.kind == "sparse":
        rows = (sds((scale.n, scale.nnz), jnp.int32, block[0]),
                sds((scale.n, scale.nnz), jnp.float32, block[1]))
    else:
        rows = (sds((scale.d, scale.n), jnp.float32, block[0]),)
    return rows + (sds((scale.n,), jnp.float32, e_spec),
                   sds((scale.n,), jnp.float32, e_spec),
                   sds((scale.d,), jnp.float32, v_spec),
                   jax.ShapeDtypeStruct((), jnp.int32))


def lower_glm(arch: str, mesh):
    """Lower a GLM epoch program: named config or registry dataset.

    `arch` is a GLM_CONFIGS key ("glm-criteo", ...) or a dataset
    registry name ("higgs", "criteo-kaggle-sub", ...), which is sized
    via `scale_for_dataset`."""
    scale = (GLM_CONFIGS[arch] if arch in GLM_CONFIGS
             else scale_for_dataset(arch))
    make = make_sparse_epoch if scale.kind == "sparse" else make_dense_epoch
    epoch = make(scale, mesh)
    inputs = glm_input_specs(scale, mesh)
    return jax.jit(epoch, donate_argnums=tuple(range(len(inputs) - 1))) \
        .lower(*inputs)


# ---------------------------------------------------------------------------
# Streamed epochs on the mesh (DESIGN.md S16)
# ---------------------------------------------------------------------------


def _as_mesh_feed(source, mesh, *, ex_axes, tp, model_axis, model_lanes,
                  d_loc, verify, width) -> engine.MeshChunkFeed:
    """Coerce any streamable source into a mesh-sharded chunk feed.

    Accepts a `TileCache`, a `TileFeed` (its verify flag carries over),
    an `ArrayFeed`-style host-array holder, a ready `MeshChunkFeed`, or
    a `ResilientChunkFeed` wrapping any of those — in the resilient
    case the INNER feed is upgraded in place, so retry/quarantine/
    rebuild semantics keep guarding the mesh path (`rebind` keeps the
    sharded feed alive across a cache rebuild).
    """
    from repro.data.cache import TileCache, TileFeed
    from repro.resilience.feed import ResilientChunkFeed

    def wrap(src, v):
        return engine.MeshChunkFeed(
            src, mesh, ex_axes=ex_axes, tp=tp, model_axis=model_axis,
            model_lanes=model_lanes, d_loc=d_loc, verify=v, width=width)

    if isinstance(source, engine.MeshChunkFeed):
        return source
    if isinstance(source, ResilientChunkFeed):
        inner = source.feed
        if not isinstance(inner, engine.MeshChunkFeed):
            if isinstance(inner, TileFeed):
                source.feed = wrap(inner.cache, verify or inner.verify)
            else:
                source.feed = wrap(inner, verify)
        return source
    if isinstance(source, TileCache):
        return wrap(source, verify)
    if isinstance(source, TileFeed):
        return wrap(source.cache, verify or source.verify)
    if hasattr(source, "y") and (hasattr(source, "X")
                                 or hasattr(source, "idx")):
        return wrap(source, verify)
    raise TypeError(
        f"cannot stream a {type(source).__name__} onto a mesh — pass a "
        f"TileCache, TileFeed, ArrayFeed, MeshChunkFeed, or a "
        f"ResilientChunkFeed wrapping one")


def make_streamed_epoch_mesh(scale: GLMScale, mesh, source,
                             obj: Objective = LOGISTIC, *,
                             interpret: bool | None = None,
                             journal=None, verify: bool = False,
                             width: int | None = None,
                             damp: float = 1.0, stats: dict | None = None,
                             jit_step: bool = True):
    """-> epoch_fn(alpha, v, epoch) streaming `source` onto the mesh.

    The mesh twin of `engine.make_streamed_epoch`: the SAME chunk loop
    (`run_epoch_streamed` — double buffering, journal hooks, stats)
    drives a shard_map'd chunk step, with `engine.MeshSchedule`
    mirroring the resident mesh's re-deal + visit PRNG streams on the
    host and `engine.MeshChunkFeed` landing each chunk pre-sharded.
    Under ``deterministic=True`` the result is bitwise-identical to
    resident mesh training (`make_dense_epoch`/`make_sparse_epoch`) on
    the same (seed, epoch) — pinned by tests/test_mesh_stream.py —
    while only ever holding `chunks`-th of the examples on device.

    Feature-sharded sparse scales stream slice-compacted per-lane
    feeds through `TileCache.slice_gather` (each model lane transfers
    only its d/M feature slice, ~M-fold fewer per-lane H2D bytes; the
    step reassembles exact rows on device).  `alpha` and `v` follow
    the global-array convention of the streamed sim path: alpha (n,)
    replicated, v (d,) — P('model')-sharded for dense TP.

    ``journal`` threads an `EpochJournal` (chunk-cursor crash resume,
    bitwise replay); ``stats`` a dict collecting the epoch's ingest
    overlap metrics; ``damp`` the health guard's dv_scale multiplier;
    ``verify``/``width`` forward to the feed.  The returned closure
    exposes ``.feed`` and ``.schedule``.
    """
    ex_axes, _, _, tp = _axes(mesh, scale)
    W = _worker_count(mesh, scale)
    spec = scale.engine_config(mesh)
    coll = _collectives(mesh, scale)
    sparse = scale.kind == "sparse"
    sparse_tp = sparse and scale.feature_shard \
        and "model" in mesh.axis_names
    model_axis = "model" if (tp or sparse_tp) else None
    model_lanes = mesh.shape["model"] if sparse_tp else None
    d_loc = None
    if sparse_tp:
        from repro.kernels import ops as kops
        d_loc = kops.sparse_slice_width(scale.d, model_lanes)
    feed = _as_mesh_feed(source, mesh, ex_axes=ex_axes, tp=tp,
                         model_axis=model_axis, model_lanes=model_lanes,
                         d_loc=d_loc, verify=verify, width=width)
    if feed.n != scale.n or feed.bucket != scale.bucket:
        raise ValueError(
            f"feed shape mismatch: feed has n={feed.n} bucket="
            f"{feed.bucket}, scale wants n={scale.n} bucket="
            f"{scale.bucket}")
    cache_backed = getattr(feed, "cache", None) is not None
    solver = engine.make_local_solver(
        scale.local_solver, obj, scale.lam * scale.n,
        spec.sigma_prime(W), bucket=scale.bucket, sparse=sparse,
        model_axis=model_axis,
        model_lanes=model_lanes, interpret=interpret,
        source=("tile cache (mesh-streamed)" if cache_backed
                else "array feed (mesh-streamed)"))
    dv_scale = (1.0 / W if scale.aggregation == "averaging"
                else 1.0) * damp
    step = engine.make_mesh_streamed_step(
        mesh, coll, solver, spec.algo, ex_axes=ex_axes, sparse=sparse,
        tp=tp, slice_lanes=model_lanes, model_axis="model",
        nnz=(feed.nnz if sparse else None), dv_scale=dv_scale,
        jit=jit_step)
    sched = engine.MeshSchedule(
        scale.n // scale.bucket, pods=mesh.shape.get("pod", 1),
        data=mesh.shape.get("data", 1),
        model=mesh.shape.get("model", 1),
        model_in_lanes=("model" in ex_axes), seed=scale.seed,
        redeal=(scale.partition != "static"),
        redeal_frac=scale.redeal_frac)
    driver = engine.MeshStreamDriver(mesh, coll, tp=tp)

    def epoch_fn(alpha, v, epoch):
        return engine.run_epoch_streamed(
            driver, feed, step, sched, spec.algo, alpha, v, epoch,
            journal=journal, stats=stats)

    epoch_fn.feed = feed
    epoch_fn.schedule = sched
    return epoch_fn


# ---------------------------------------------------------------------------
# Analytic per-epoch cost (GLM epochs scan coordinates inside while loops,
# which XLA:CPU's cost_analysis counts once — see counting.py; the closed
# form below is exact for this algorithm and is used for the roofline)
# ---------------------------------------------------------------------------

# logistic delta: NEWTON_STEPS evaluations of h and their guarded
# steps, 17 FLOPs each (exp and reciprocal counted as one)
_DELTA_FLOPS = NEWTON_STEPS * 17


def glm_analytic(scale: GLMScale, mesh, *, streamed: bool = False) -> dict:
    """Per-device per-epoch {flops, bytes accessed, coll} estimates.

    ``streamed=True`` adds an "h2d bytes" entry — the host->device
    ingest bytes a `MeshChunkFeed` ships per device-epoch, taken from
    `core.planner.streamed_transfer_bytes` (the one h2d model) and
    reported SEPARATELY from HBM traffic: the host link is ~50x slower
    than HBM, so folding ingest into "bytes accessed" would corrupt
    the roofline's memory-bound term."""
    W = _worker_count(mesh, scale)
    ex_axes, sync_axes, has_pod, tp = _axes(mesh, scale)
    n_local = scale.n // W
    B = scale.bucket
    nb = n_local // B
    d_loc = scale.d // mesh.shape["model"] if tp else scale.d

    if scale.kind == "dense":
        # per bucket: margins 2*d_loc*B + Gram d_loc*B^2 + v-update
        # 2*d_loc*B + recursion B * (B axpy + delta)
        per_bucket = (2 * d_loc * B + d_loc * B * B + 2 * d_loc * B
                      + B * (2 * B + _DELTA_FLOPS))
        flops = nb * per_bucket
        x_bytes = d_loc * n_local * 4
        # X streamed once per chunked pass + rotated once (read+write)
        bytes_acc = x_bytes * 3 + scale.chunks * d_loc * 4 * 2
    else:
        per_coord = (2 * scale.nnz * 3 + _DELTA_FLOPS)
        flops = n_local * per_coord
        x_bytes = n_local * scale.nnz * 8
        bytes_acc = x_bytes * 3 + n_local * scale.nnz * 4 * 2  # v gather/scatter
    # collectives (result-shape convention, per device):
    #   chunk reductions of dv over sync axes (f32 all-reduce: 4 B/elem;
    #   int8 two-phase: ~2 B/elem) + the bucket re-deal (all-to-all of
    #   redeal_frac of the local shard) + cross-pod int8 all-gather
    sync_bytes = 2 if scale.compress_sync else 4
    dv_len = scale.d if scale.kind == "sparse" else d_loc
    coll = scale.chunks * dv_len * sync_bytes * len(sync_axes)
    coll += (x_bytes + n_local * 4 * 2) * scale.redeal_frac
    if scale.kind == "sparse" and scale.feature_shard:
        # sharded-v solver: one working-set all-gather per bucket over
        # 'model' — (M, B, nnz) f32 landing on every lane
        M = mesh.shape.get("model", 1)
        coll += (n_local // B) * M * B * scale.nnz * 4
    if has_pod:
        coll += (scale.d if scale.kind == "sparse" else d_loc) * 1 * \
            mesh.shape.get("pod", 1)               # int8 payload gather
    out = {"flops": float(flops), "bytes accessed": float(bytes_acc),
           "coll": float(coll), "method": "analytic-closed-form"}
    if streamed:
        from repro.core import planner
        pods = mesh.shape.get("pod", 1)
        topo = planner.Topology(
            backend="tpu", device_count=mesh.size, pods=pods,
            lanes=W // pods,
            model_lanes=(mesh.shape.get("model", 1)
                         if scale.feature_shard else 1))
        sig = planner.WorkloadSignature(
            n=scale.n, d=scale.d, nnz=scale.nnz,
            sparse=scale.kind == "sparse", streamed=True)
        plan = planner.SolverPlan(
            solver="xla", route="xla", bucket=scale.bucket,
            chunks=scale.chunks, nnz_multiple=8,
            feature_shard=scale.feature_shard)
        out["h2d bytes"] = planner.streamed_transfer_bytes(
            sig, topo, plan)
    return out


def glm_model_flops(scale: GLMScale, mesh) -> float:
    """Useful work per device-epoch: one pass of coordinate updates.

    For SDCA the 'model flops' are the margin + v-update inner products:
    4*d*nnz-equivalents per coordinate — the irreducible work of one
    epoch of the sequential algorithm, divided over chips.
    """
    W = _worker_count(mesh, scale)
    n_local = scale.n // W
    if scale.kind == "sparse":
        return float(n_local * 4 * scale.nnz)
    d_loc = scale.d // mesh.shape["model"] \
        if scale.feature_shard else scale.d
    return float(n_local * 4 * d_loc)
