"""The solver engine: ONE epoch program for every backend (DESIGN.md S2).

The paper's algorithm — bucketed SDCA + dynamic bucket re-dealing +
hierarchical aggregation — is a single bulk-synchronous program:

    schedule -> re-deal -> (chunked local sub-epoch) -> sync -> pod-reduce

This module implements that program exactly once (`run_epoch`),
parametrized by two seams:

  * `Collectives` — how worker axes are realized and how workers talk.
      - `SimCollectives`: pods x lanes are *virtual* workers stacked on
        leading array axes of one process (vmap / lax.map lifting,
        stacked-axis reductions).  Used by `GLMTrainer`, `cocoa.epoch_sim`
        and every benchmark.
      - `MeshCollectives`: workers are shards of a ("pod","data","model")
        device mesh; the same calls become all_to_all / all_gather / psum
        (used from inside shard_map by `launch/glm.py`).
  * `LocalSolver` — how one worker solves its chunk: dense XLA
    (`sdca.dense_local_subepoch`), dense Pallas
    (`kernels.ops.sdca_bucket_subepoch`), sparse XLA
    (`sdca.sparse_local_subepoch`), or sparse Pallas
    (`kernels.ops.sdca_sparse_bucket_subepoch` — the VMEM-resident
    shared-vector kernel over cached CSR tiles, DESIGN.md S11).
    "auto" picks Pallas on TPU backends and XLA elsewhere; the
    `$REPRO_LOCAL_SOLVER` env var overrides either way.

Bit-determinism: with `DeploymentConfig.deterministic=True` both
backends run each worker's sub-epoch UNBATCHED (lax.map in the sim;
shard programs are unbatched by construction) and reduce with ordered
gather-sums instead of psum, so `SimCollectives` and `MeshCollectives`
produce bitwise-identical (alpha, v) for the same (seed, epoch) — the
property the sim<->mesh equivalence test in tests/test_engine.py pins.
The contract holds when the simulator's lane axis mirrors the mesh's
example-parallel layout, i.e. P pods x K data lanes with model=1 (or a
feature-sharded model axis, which carries no examples).  When workers
also span 'model' (sparse / narrow-dense meshes with model>1), the
mesh re-deals only over 'data' within each model group and reduces
data-then-model, which the flat sim lane axis does not mirror — sim
runs there are convergence-equivalent, not bitwise.

Worker PRNG streams are derived identically on both backends:

    worker_key = fold(fold(fold(PRNGKey(seed), epoch), pod), lane)
    re-deal perm   <- fold(worker_key, 0)
    visit-order    <- fold(worker_key, 1)

with `lane` counted data-major over the example-parallel axes.
"""
from __future__ import annotations

import dataclasses
import os
import time
import warnings
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Mapping, Optional, Protocol, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from . import sdca
from .config import AlgoConfig, EngineConfig, as_engine_config
from .objectives import Objective

Array = jax.Array

# check_vma=False: v is *mathematically* invariant over unmentioned axes
# (every lane adds the same reduced delta to the same replica), but the
# static VMA tracker cannot see through the chunked carry + the int8
# all-gather pod reduce, so we assert replication via out_specs instead.
# Lives here (not launch/glm.py) since the mesh-streamed step below
# needs it too; launch/glm.py re-imports it.
def shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

# ---------------------------------------------------------------------------
# Worker-local data blocks
# ---------------------------------------------------------------------------


def _row_ids(rid):
    """A block's optional row ids (*w, n_local) as re-deal entries."""
    return () if rid is None else ((rid, -1),)


@dataclasses.dataclass(frozen=True)
class DenseBlock:
    """Dense worker-local examples: X (*w, d_shard, n_local).  `rid`,
    where given, holds each example's row in the caller's data and is
    re-dealt with it, so a re-dealt layout can be read back in row
    order."""
    X: Array
    rid: Optional[Array] = None

    @property
    def n_local(self) -> int:
        return self.X.shape[-1]

    def take(self, cols: Array):
        return jnp.take_along_axis(self.X, cols[..., None, :], axis=-1)

    def arrs(self):
        return ((self.X, -1),) + _row_ids(self.rid)

    def rebuild(self, arrs) -> "DenseBlock":
        return DenseBlock(*arrs)


@dataclasses.dataclass(frozen=True)
class SparseBlock:
    """Padded-CSR worker-local examples: idx/val (*w, n_local, nnz),
    with optional row ids as in `DenseBlock`."""
    idx: Array
    val: Array
    rid: Optional[Array] = None

    @property
    def n_local(self) -> int:
        return self.idx.shape[-2]

    def take(self, cols: Array):
        return (jnp.take_along_axis(self.idx, cols[..., :, None], axis=-2),
                jnp.take_along_axis(self.val, cols[..., :, None], axis=-2))

    def arrs(self):
        return ((self.idx, -2), (self.val, -2)) + _row_ids(self.rid)

    def rebuild(self, arrs) -> "SparseBlock":
        return SparseBlock(*arrs)


Block = Union[DenseBlock, SparseBlock]

# ---------------------------------------------------------------------------
# Local solvers (the per-worker sub-epoch)
# ---------------------------------------------------------------------------


class LocalSolver(Protocol):
    """One worker's pass over its chunk: (data, y, a, v) -> (a_new, dv).

    `data` is an X tile (d_shard, nc) for dense solvers or an
    (idx, val) row pair for sparse ones; `dv` is the UNSCALED global
    delta (CoCoA+ convention).
    """

    def __call__(self, data, y: Array, a: Array, v: Array
                 ) -> tuple[Array, Array]: ...


def dense_xla_solver(obj: Objective, lam_n, sig, bucket: int,
                     model_axis: Optional[str] = None) -> LocalSolver:
    def solve(X, y, a, v):
        return sdca.dense_local_subepoch(
            obj, X, y, a, v, jnp.asarray(lam_n, X.dtype),
            jnp.asarray(sig, X.dtype), bucket, model_axis=model_axis)
    return solve


def dense_pallas_solver(obj: Objective, lam_n, sig, bucket: int,
                        interpret: Optional[bool] = None,
                        source: str = "ad-hoc arrays") -> LocalSolver:
    from repro.kernels import ops as kops

    def solve(X, y, a, v):
        return kops.sdca_bucket_subepoch(
            obj, X, y, a, v, jnp.asarray(lam_n, X.dtype),
            jnp.asarray(sig, X.dtype), bucket=bucket, interpret=interpret,
            source=source)
    return solve


def sparse_solver(obj: Objective, lam_n, sig) -> LocalSolver:
    def solve(data, y, a, v):
        idx, val = data
        return sdca.sparse_local_subepoch(
            obj, idx, val, y, a, v, jnp.asarray(lam_n, val.dtype),
            jnp.asarray(sig, val.dtype))
    return solve


def sparse_pallas_solver(obj: Objective, lam_n, sig, bucket: int,
                         interpret: Optional[bool] = None,
                         source: str = "ad-hoc arrays") -> LocalSolver:
    from repro.kernels import ops as kops

    def solve(data, y, a, v):
        idx, val = data
        return kops.sdca_sparse_bucket_subepoch(
            obj, idx, val, y, a, v, jnp.asarray(lam_n, val.dtype),
            jnp.asarray(sig, val.dtype), bucket=bucket,
            interpret=interpret, source=source)
    return solve


def sparse_sharded_pallas_solver(obj: Objective, lam_n, sig, bucket: int,
                                 model_axis: str, model_lanes: int,
                                 interpret: Optional[bool] = None,
                                 source: str = "ad-hoc arrays"
                                 ) -> LocalSolver:
    """Feature-sharded sparse kernel: each `model_axis` lane owns a
    d/model_lanes slice of v and the per-bucket working-set exchange
    happens inside the sub-epoch (kernels/ops.py, DESIGN.md S12).  dv
    has support only on the lane's slice, so the engine's ordered sync
    over the model axis reassembles the serial dv bitwise."""
    from repro.kernels import ops as kops

    def solve(data, y, a, v):
        idx, val = data
        return kops.sdca_sparse_sharded_subepoch(
            obj, idx, val, y, a, v, jnp.asarray(lam_n, val.dtype),
            jnp.asarray(sig, val.dtype), bucket=bucket,
            model_axis=model_axis, model_lanes=model_lanes,
            interpret=interpret, source=source)
    return solve


def sparse_sharded_xla_solver(obj: Objective, lam_n, sig,
                              model_axis: str, model_lanes: int
                              ) -> LocalSolver:
    """The sharded kernel's XLA twin on the SAME feature-sharded
    layout: run the full HBM-resident scan, then zero dv outside this
    lane's slice (`kops.sparse_slice_width` — the kernel's exact
    partition).  Masking is bitwise-free (kept entries are untouched,
    dropped entries are exact zeros), and without it every lane would
    contribute the FULL dv and the model-axis sync would count it
    `model_lanes` times."""
    from repro.kernels import ops as kops

    def solve(data, y, a, v):
        idx, val = data
        a_new, dv = sdca.sparse_local_subepoch(
            obj, idx, val, y, a, v, jnp.asarray(lam_n, val.dtype),
            jnp.asarray(sig, val.dtype))
        d_loc = kops.sparse_slice_width(v.shape[-1], model_lanes)
        # audit: collective-ok owner-slice offset for the masked update
        lo = jax.lax.axis_index(model_axis).astype(jnp.int32) \
            * jnp.int32(d_loc)
        j = jnp.arange(v.shape[-1], dtype=jnp.int32)
        own = jnp.logical_and(j >= lo, j < lo + d_loc)
        return a_new, jnp.where(own, dv, jnp.zeros((), dv.dtype))
    return solve


def _resolve_auto() -> tuple[str, bool]:
    """("xla"|"pallas", explicit?) for `local_solver="auto"` — explicit
    when the `$REPRO_LOCAL_SOLVER` hatch forced the choice.  The ONLY
    parser of the env hatch."""
    env = os.environ.get("REPRO_LOCAL_SOLVER", "").strip().lower()
    if env:
        if env not in ("xla", "pallas"):
            raise ValueError(
                f"$REPRO_LOCAL_SOLVER={env!r}: must be 'xla' or 'pallas'")
        return env, True
    return ("pallas" if jax.default_backend() == "tpu" else "xla"), False


def resolve_auto_solver() -> str:
    """What `local_solver="auto"` means here: "pallas" on TPU backends
    (dense AND sparse — both kernels exist), "xla" everywhere else.
    `$REPRO_LOCAL_SOLVER=xla|pallas` overrides in either direction
    (the escape hatch for unprofiled TPU topologies / forcing the
    interpret-mode kernel on CPU)."""
    return _resolve_auto()[0]


def _auto_fallback(pallas_solve: LocalSolver, xla_solve: LocalSolver,
                   misfit: Callable, warn_path: str) -> LocalSolver:
    """Backend-auto pallas: pre-check the workload's static shapes
    against the kernel contract at trace time (`misfit(data, v) ->
    reason | None`) and route misfits to the XLA path instead of
    raising mid-trace.  Explicit `local_solver="pallas"` (config or
    $REPRO_LOCAL_SOLVER) skips this and keeps the kernel's actionable
    errors."""
    def solve(data, y, a, v):
        why = misfit(data, v)
        if why is None:
            return pallas_solve(data, y, a, v)
        warnings.warn(
            f"local_solver='auto': the {warn_path} Pallas kernel "
            f"cannot run this workload ({why}); using the XLA path "
            f"instead.  Set $REPRO_LOCAL_SOLVER=pallas to force the "
            f"kernel and get the full error.", stacklevel=2)
        return xla_solve(data, y, a, v)
    return solve


def _sparse_auto_fallback(obj: Objective, lam_n, sig, bucket: int,
                          pallas_solve: LocalSolver) -> LocalSolver:
    from repro.core import planner

    def misfit(data, v):
        idx, _ = data
        _, why = planner.route_sparse(
            idx.shape[-2], idx.shape[-1], v.shape[-1], bucket)
        return why
    return _auto_fallback(pallas_solve, sparse_solver(obj, lam_n, sig),
                          misfit, "sparse")


def _sparse_sharded_auto_fallback(obj: Objective, lam_n, sig, bucket: int,
                                  model_axis: str, model_lanes: int,
                                  pallas_solve: LocalSolver) -> LocalSolver:
    """Sharded-layout twin of `_sparse_auto_fallback`: the misfit check
    carries `model_lanes` (sharded feasibility) and the fallback is the
    slice-MASKED scan — the layout already commits every lane to owning
    only its dv slice."""
    from repro.core import planner

    def misfit(data, v):
        idx, _ = data
        _, why = planner.route_sparse(
            idx.shape[-2], idx.shape[-1], v.shape[-1], bucket,
            model_lanes=model_lanes)
        return why
    return _auto_fallback(
        pallas_solve,
        sparse_sharded_xla_solver(obj, lam_n, sig, model_axis,
                                  model_lanes),
        misfit, "feature-sharded sparse")


def _dense_auto_fallback(obj: Objective, lam_n, sig, bucket: int,
                         pallas_solve: LocalSolver) -> LocalSolver:
    from repro.core import planner

    def misfit(X, v):
        return planner.route_dense(X.shape[-2], X.shape[-1], bucket)
    return _auto_fallback(pallas_solve,
                          dense_xla_solver(obj, lam_n, sig, bucket),
                          misfit, "dense")


def make_local_solver(kind: str, obj: Objective, lam_n, sig, *,
                      bucket: int = 1, sparse: bool = False,
                      model_axis: Optional[str] = None,
                      model_lanes: Optional[int] = None,
                      interpret: Optional[bool] = None,
                      source: str = "ad-hoc arrays") -> LocalSolver:
    """Resolve an `AlgoConfig.local_solver` name to a LocalSolver.

    "auto" resolves via `resolve_auto_solver`: "pallas" on TPU backends
    for BOTH the dense and sparse paths, "xla" elsewhere, with
    `$REPRO_LOCAL_SOLVER` as the override.  Unknown kinds are rejected
    everywhere.  Backend-picked auto's per-workload misfit pre-checks
    route through `core.planner.route_sparse`/`route_dense` (DESIGN.md
    S13) — pure delegations to the kernels' own predicates, so plans
    can never loosen feasibility and `$REPRO_PLAN` never changes the
    fallback verdicts here.

    Feature sharding: `model_axis` + `model_lanes` on the SPARSE path
    select the sharded-v layout (DESIGN.md S12) — "pallas" runs the
    model-axis sharded kernel, "xla" the slice-masked scan, and a
    backend-picked "auto" wraps the kernel with a sharded-feasibility
    check (`kops.sparse_kernel_misfit(..., model_lanes=...)`) that
    falls back to the masked scan.  Dense feature sharding (model-axis
    psum inside the sub-epoch) still has no kernel, as does the legacy
    sparse layout that passes `model_axis` WITHOUT `model_lanes` (the
    model axis as an example axis): a backend-picked "auto" quietly
    keeps the previously-working "xla" route there, while an explicit
    pallas request (config or env var) raises.  A backend-picked
    "auto" likewise falls back to "xla" per-workload (dense AND
    sparse) when the shapes violate the kernel contract (alignment,
    bucket cap, VMEM budgets) instead of failing at epoch build.
    `source` labels the data provenance (tile cache vs ad-hoc arrays)
    in kernel alignment errors.
    """
    auto_pick = False
    if kind == "auto":
        # backend-picked only if the env hatch is unset: a user-forced
        # $REPRO_LOCAL_SOLVER=pallas is an explicit request and keeps
        # the loud failure modes below.
        kind, explicit = _resolve_auto()
        auto_pick = not explicit
    if kind not in ("xla", "pallas"):
        raise ValueError(f"unknown local_solver {kind!r}")
    sharded_sparse = (sparse and model_axis is not None
                      and model_lanes is not None)
    if kind == "pallas" and model_axis is not None and not sharded_sparse:
        if auto_pick:
            kind = "xla"
        else:
            raise ValueError(
                "local_solver='pallas' does not support feature "
                "sharding (model-axis psum) on this path yet"
                + ("; pass model_lanes=... to route the sparse path "
                   "through the sharded-v kernel" if sparse else ""))
    if sparse:
        if sharded_sparse:
            if kind == "pallas":
                pallas = sparse_sharded_pallas_solver(
                    obj, lam_n, sig, bucket, model_axis, model_lanes,
                    interpret=interpret, source=source)
                if auto_pick:
                    return _sparse_sharded_auto_fallback(
                        obj, lam_n, sig, bucket, model_axis,
                        model_lanes, pallas)
                return pallas
            return sparse_sharded_xla_solver(obj, lam_n, sig,
                                             model_axis, model_lanes)
        if kind == "pallas":
            pallas = sparse_pallas_solver(obj, lam_n, sig, bucket,
                                          interpret=interpret,
                                          source=source)
            if auto_pick:
                return _sparse_auto_fallback(obj, lam_n, sig, bucket,
                                             pallas)
            return pallas
        return sparse_solver(obj, lam_n, sig)
    if kind == "pallas":
        pallas = dense_pallas_solver(obj, lam_n, sig, bucket,
                                     interpret=interpret, source=source)
        if auto_pick:
            return _dense_auto_fallback(obj, lam_n, sig, bucket, pallas)
        return pallas
    return dense_xla_solver(obj, lam_n, sig, bucket, model_axis=model_axis)


# ---------------------------------------------------------------------------
# Wire compression helpers (the ONLY home of this logic)
# ---------------------------------------------------------------------------


def q_psum(x: Array, axis_name: str, size: int) -> Array:
    """int8 two-phase reduction over `axis_name` (quantized
    reduce-scatter then quantized all-gather): ~2 bytes/element on the
    wire instead of all-reduce's ~8 — the glm-criteo SPerf iteration.
    """
    from repro.optim.compression import compress
    if size <= 1:
        return x
    n = x.shape[0]
    pad = (-n) % size
    if pad:
        x = jnp.pad(x, (0, pad))
    qz, _ = compress(x)
    # phase 1: exchange int8 shards, sum locally in f32
    # audit: collective-ok pure data movement; the sum is ordered jnp.sum
    shards = jax.lax.all_to_all(
        qz.q.reshape(size, -1), axis_name, split_axis=0, concat_axis=0,
        tiled=False)                                  # (size, n/size)
    scales = jax.lax.all_gather(qz.scale, axis_name)  # audit: collective-ok
    part = jnp.sum(shards.astype(jnp.float32)
                   * scales.reshape(size, 1), axis=0)  # my shard, reduced
    # phase 2: int8 all-gather of the reduced shards
    qz2, _ = compress(part)
    q_all = jax.lax.all_gather(qz2.q, axis_name)  # audit: collective-ok
    s_all = jax.lax.all_gather(qz2.scale, axis_name)  # audit: collective-ok
    out = (q_all.astype(jnp.float32)
           * s_all.reshape(size, 1)).reshape(x.shape)
    return out[:n] if pad else out


def _quantize_roundtrip(x: Array, axis: int) -> Array:
    """Model the int8 wire: per-worker quantize/dequantize along `axis`."""
    from repro.optim.compression import compress, dequantize
    qz, _ = compress(x, axis=axis)
    return dequantize(qz)


# ---------------------------------------------------------------------------
# Collectives backends
# ---------------------------------------------------------------------------


class Collectives(Protocol):
    """How worker axes are realized and how workers communicate.

    `wshape` is the leading stacked worker shape of every array the
    engine touches: (pods, lanes) for the simulator, () inside a
    shard_map where each program instance IS one worker.
    """
    wshape: tuple[int, ...]

    def worker_keys(self, seed: int, epoch): ...
    def map_workers(self, fn: Callable, args: tuple): ...
    def visit_perms(self, keys, nb_local: int): ...
    def broadcast_ids(self, ids: Array): ...
    def redeal(self, arrs, nb_local: int, keys, frac: float): ...
    def pod_replicate(self, v: Array): ...
    def worker_view(self, v: Array): ...
    def lane_sum(self, dv: Array, compress: bool = False): ...
    def pod_reduce(self, v_new: Array, v_in: Array): ...


@dataclasses.dataclass(frozen=True)
class SimCollectives:
    """pods x lanes virtual workers stacked on leading array axes.

    deterministic=True runs each worker's sub-epoch unbatched via
    lax.map (identical HLO to a mesh shard program) instead of vmap;
    reductions are ordered sums either way.
    """
    pods: int = 1
    lanes: int = 1
    deterministic: bool = False
    compress_pod: bool = False

    @property
    def wshape(self) -> tuple[int, ...]:
        return (self.pods, self.lanes)

    def worker_keys(self, seed, epoch):
        base = jax.random.fold_in(jax.random.PRNGKey(seed),
                                  jnp.asarray(epoch, jnp.int32))
        pods = jnp.arange(self.pods, dtype=jnp.int32)
        lanes = jnp.arange(self.lanes, dtype=jnp.int32)
        per_pod = jax.vmap(lambda p: jax.random.fold_in(base, p))(pods)
        return jax.vmap(lambda kp: jax.vmap(
            lambda l: jax.random.fold_in(kp, l))(lanes))(per_pod)

    def _flat(self, tree):
        W = self.pods * self.lanes
        return jax.tree.map(lambda x: x.reshape((W,) + x.shape[2:]), tree)

    def _unflat(self, tree):
        return jax.tree.map(
            lambda x: x.reshape((self.pods, self.lanes) + x.shape[1:]),
            tree)

    def map_workers(self, fn, args):
        flat = self._flat(args)
        if self.deterministic:
            out = jax.lax.map(lambda xs: fn(*xs), flat)
        else:
            out = jax.vmap(fn)(*flat)
        return self._unflat(out)

    def visit_perms(self, keys, nb_local):
        def one(k):
            return jax.random.permutation(
                jax.random.fold_in(k, 1), nb_local).astype(jnp.int32)
        return self._unflat(jax.vmap(one)(self._flat(keys)))

    def broadcast_ids(self, ids):
        return jnp.broadcast_to(ids, self.wshape + ids.shape)

    def redeal(self, arrs, nb_local, keys, frac):
        """Stacked mirror of the mesh all-to-all bucket re-deal: each
        lane shuffles its buckets (per-worker key), the first `exch`
        buckets are split K ways and transposed across the lane axis —
        pure data movement, bitwise-identical to lax.all_to_all."""
        P, K = self.pods, self.lanes
        if K <= 1 or frac <= 0:
            return tuple(x for x, _ in arrs)
        exch = max(int(nb_local * frac) // K * K, K)

        def pkey(k):
            return jax.random.permutation(
                jax.random.fold_in(k, 0), nb_local).astype(jnp.int32)
        perms = self._unflat(jax.vmap(pkey)(self._flat(keys)))  # (P,K,nb)

        def one(x, ax):
            xb = jnp.moveaxis(x, ax, 2)            # (P, K, n_local, ...)
            shp = xb.shape
            rows = shp[2] // nb_local
            rest = shp[3:]
            xb = xb.reshape((P, K, nb_local, rows) + rest)
            idx = perms.reshape((P, K, nb_local)
                                + (1,) * (xb.ndim - 3))
            xb = jnp.take_along_axis(xb, idx, axis=2)
            head = xb[:, :, :exch]
            # lane j receives [split_j of lane 0, ..., split_j of lane
            # K-1] concatenated in lane order == tiled all_to_all
            head = head.reshape((P, K, K, exch // K, rows) + rest)
            head = head.swapaxes(1, 2)
            head = head.reshape((P, K, exch, rows) + rest)
            xb = jnp.concatenate([head, xb[:, :, exch:]], axis=2)
            return jnp.moveaxis(xb.reshape(shp), 2, ax)

        return tuple(one(x, ax) for x, ax in arrs)

    def pod_replicate(self, v):
        if v.ndim == 1:
            return jnp.broadcast_to(v, (self.pods,) + v.shape)
        return v

    def worker_view(self, v):
        # (P, d) pod replicas -> (P, K, d) per-worker replicas
        return jnp.broadcast_to(v[:, None, :],
                                (self.pods, self.lanes, v.shape[-1]))

    def lane_sum(self, dv, compress=False):
        """(P, K, d) worker deltas -> (P, d) per-pod ordered sums."""
        if compress:
            dv = _quantize_roundtrip(dv, axis=dv.ndim - 1)
        # per-pod sum over the lane axis: the same ordered reduction
        # the mesh backend performs on its all_gather'd stack
        # (bit-stable; pinned by the sim<->mesh equivalence tests).
        return jnp.sum(dv, axis=1)

    def pod_reduce(self, v_pods, v_in):
        if self.pods == 1:
            return v_pods[0]
        deltas = v_pods - v_in
        if self.compress_pod:
            deltas = _quantize_roundtrip(deltas, axis=deltas.ndim - 1)
        return v_in[0] + jnp.sum(deltas, axis=0)


@dataclasses.dataclass(frozen=True)
class MeshCollectives:
    """Real collectives over a ("pod","data","model") mesh; every
    method body runs INSIDE shard_map, where this program instance is
    one worker and its arrays are the local shards."""
    lane_axes: tuple[str, ...]            # example-parallel, data-major
    sync_axes: tuple[str, ...]            # chunk-sync reduction axes
    axis_sizes: Mapping[str, int]
    pod_axis: Optional[str] = None
    redeal_axis: Optional[str] = "data"
    deterministic: bool = False
    compress_pod: bool = False

    wshape: tuple[int, ...] = ()

    def _pod_size(self) -> int:
        return self.axis_sizes.get(self.pod_axis, 1) if self.pod_axis else 1

    def worker_keys(self, seed, epoch):
        base = jax.random.fold_in(jax.random.PRNGKey(seed),
                                  jnp.asarray(epoch, jnp.int32))
        # audit: collective-ok per-worker RNG key derivation
        pod = (jax.lax.axis_index(self.pod_axis).astype(jnp.int32)
               if self.pod_axis else jnp.int32(0))
        kp = jax.random.fold_in(base, pod)
        lane = jnp.int32(0)
        for ax in self.lane_axes:
            lane = lane * self.axis_sizes[ax] \
                + jax.lax.axis_index(ax).astype(jnp.int32)  # audit: collective-ok key derivation
        return jax.random.fold_in(kp, lane)

    def map_workers(self, fn, args):
        return fn(*args)

    def visit_perms(self, keys, nb_local):
        return jax.random.permutation(
            jax.random.fold_in(keys, 1), nb_local).astype(jnp.int32)

    def broadcast_ids(self, ids):
        return ids

    def redeal(self, arrs, nb_local, keys, frac):
        """Balanced all-to-all bucket re-deal over the data axis (the
        paper's dynamic partitioning, TPU-native; O(local data) ICI).
        A ring rotation of whole blocks was tried first and REFUTED —
        see core/partition.py."""
        ax_name = self.redeal_axis
        size = self.axis_sizes.get(ax_name, 1) if ax_name else 1
        if size <= 1 or frac <= 0:
            return tuple(x for x, _ in arrs)
        perm = jax.random.permutation(
            jax.random.fold_in(keys, 0), nb_local).astype(jnp.int32)
        exch = max(int(nb_local * frac) // size * size, size)

        def one(x, ax):
            xb = jnp.moveaxis(x, ax, 0)        # (n_local, ...)
            shp = xb.shape
            rows = shp[0] // nb_local
            rest = shp[1:]
            xb = xb.reshape((nb_local, rows) + rest)[perm]
            head = xb[:exch].reshape((exch * rows,) + rest)
            # audit: collective-ok bucket re-deal is pure data movement
            head = jax.lax.all_to_all(head, ax_name, split_axis=0,
                                      concat_axis=0, tiled=True)
            xb = jnp.concatenate(
                [head.reshape((exch, rows) + rest), xb[exch:]], axis=0)
            return jnp.moveaxis(xb.reshape(shp), 0, ax)

        return tuple(one(x, ax) for x, ax in arrs)

    def pod_replicate(self, v):
        return v

    def worker_view(self, v):
        return v

    def lane_sum(self, dv, compress=False):
        for ax in self.sync_axes:
            size = self.axis_sizes.get(ax, 1)
            if size <= 1:
                continue
            if compress:
                dv = q_psum(dv, ax, size)
            elif self.deterministic:
                # ordered gather-sum: bit-stable and identical to the
                # simulator's stacked reduction
                # audit: collective-ok ordered gather-sum (bit-stable)
                dv = jnp.sum(jax.lax.all_gather(dv, ax), axis=0)
            else:
                # audit: collective-ok deterministic=False path only
                dv = jax.lax.psum(dv, ax)
        return dv

    def pod_reduce(self, v_new, v_in):
        """Cross-pod combine of per-pod v deltas (optionally int8)."""
        if self._pod_size() <= 1:
            return v_new
        dv = v_new - v_in
        if self.compress_pod:
            from repro.optim.compression import compress
            qz, _err = compress(dv)    # EF residual handled by caller state
            # audit: collective-ok int8 wire gather; sum is ordered
            q_all = jax.lax.all_gather(qz.q, self.pod_axis)
            s_all = jax.lax.all_gather(qz.scale, self.pod_axis)  # audit: collective-ok
            dv_sum = jnp.sum(q_all.astype(jnp.float32)
                             * s_all.reshape((-1,) + (1,) * dv.ndim),
                             axis=0)
        elif self.deterministic:
            # audit: collective-ok ordered gather-sum (bit-stable)
            dv_sum = jnp.sum(jax.lax.all_gather(dv, self.pod_axis), axis=0)
        else:
            # audit: collective-ok deterministic=False path only
            dv_sum = jax.lax.psum(dv, self.pod_axis)
        return v_in + dv_sum


# ---------------------------------------------------------------------------
# The epoch program (the only copy)
# ---------------------------------------------------------------------------


def _apply_chunk(coll: Collectives, solver: LocalSolver, algo: AlgoConfig,
                 data, yc: Array, ac: Array, v_c: Array, *,
                 straggler_mask: Optional[Array] = None,
                 dv_scale: float = 1.0) -> tuple[Array, Array]:
    """One chunk's solve/mask/sync — shared by the resident-block loop
    (`run_epoch`) and the out-of-core loop (`run_epoch_streamed`), so
    the two paths are the same program on the same inputs."""
    a_new, dv = coll.map_workers(solver,
                                 (data, yc, ac, coll.worker_view(v_c)))
    if straggler_mask is not None:
        a_new = jnp.where(straggler_mask[..., None], a_new, ac)
        dv = dv * straggler_mask[..., None].astype(dv.dtype)
    if dv_scale != 1.0:
        dv = dv * jnp.asarray(dv_scale, dv.dtype)
    return a_new, v_c + coll.lane_sum(dv, compress=algo.compress_sync)


def _put_cols(a: Array, cols: Array, vals: Array) -> Array:
    """alpha[..., cols] = vals with optional leading worker axes."""
    if a.ndim == 1:
        return a.at[cols].set(vals)
    lead = a.shape[:-1]
    fa = a.reshape((-1, a.shape[-1]))
    fc = cols.reshape((-1, cols.shape[-1]))
    fv = vals.reshape((-1, vals.shape[-1]))
    out = jax.vmap(lambda ai, ci, vi: ai.at[ci].set(vi))(fa, fc, fv)
    return out.reshape(lead + (a.shape[-1],))


def run_epoch(
    coll: Collectives,
    solver: LocalSolver,
    algo: AlgoConfig,
    block: Block,
    y: Array,
    a: Array,
    v: Array,
    epoch,
    *,
    straggler_mask: Optional[Array] = None,   # (*wshape) True = alive
    redeal: bool = True,
    visit_shuffle: bool = True,
    dv_scale: float = 1.0,
) -> tuple[Block, Array, Array, Array]:
    """One bulk-synchronous epoch over worker-local data.

    schedule/re-deal -> per-chunk: local sub-epoch, straggler mask,
    lane sync -> per-epoch: pod reduce.  Returns the (possibly
    re-dealt) block and labels so physical layouts persist across
    epochs, plus updated (alpha_local, v).
    """
    n_local = block.n_local
    B = algo.bucket
    if n_local % B:
        raise ValueError(f"n_local={n_local} not divisible by bucket={B}")
    nb_local = n_local // B
    chunks = algo.chunks
    if nb_local % chunks:
        raise ValueError(
            f"chunks={chunks} must divide local bucket count {nb_local}")
    per_chunk = nb_local // chunks

    keys = coll.worker_keys(algo.seed, epoch)
    if redeal:
        arrs = block.arrs() + ((y, -1), (a, -1))
        out = coll.redeal(arrs, nb_local, keys, algo.redeal_frac)
        nblk = len(block.arrs())
        block = block.rebuild(out[:nblk])
        y, a = out[nblk], out[nblk + 1]
    if visit_shuffle:
        perm = coll.visit_perms(keys, nb_local)
    else:
        perm = coll.broadcast_ids(jnp.arange(nb_local, dtype=jnp.int32))

    v = coll.pod_replicate(v)
    v_in = v
    barange = jnp.arange(B, dtype=jnp.int32)

    def chunk(c, carry):
        a_c, v_c = carry
        ids = jax.lax.slice_in_dim(
            perm, c * per_chunk, (c + 1) * per_chunk, axis=perm.ndim - 1)
        cols = (ids[..., None] * B + barange).reshape(
            ids.shape[:-1] + (per_chunk * B,))
        data = block.take(cols)
        yc = jnp.take_along_axis(y, cols, -1)
        ac = jnp.take_along_axis(a_c, cols, -1)
        a_new, v_c = _apply_chunk(
            coll, solver, algo, data, yc, ac, v_c,
            straggler_mask=straggler_mask, dv_scale=dv_scale)
        return _put_cols(a_c, cols, a_new), v_c

    # The chunk loop is unrolled (chunks is a small static count, <= ~8).
    # On jax 0.4.x a lax.fori_loop here miscompiled under shard_map:
    # closed-over values derived from axis_index (the per-lane visit
    # perm) were treated as loop-invariant-replicated and every lane
    # silently ran lane 0's visit order — the pre-engine distributed
    # driver had exactly this latent bug.  The sim<->mesh equivalence
    # test (tests/test_engine.py) pins the fixed behaviour.
    for c in range(chunks):
        a, v = chunk(c, (a, v))
    v = coll.pod_reduce(v, v_in)
    return block, y, a, v


def sharded_epoch(
    obj: Objective,
    spec: EngineConfig,
    coll: Collectives,
    block: Block,
    y: Array,
    a: Array,
    v: Array,
    epoch,
    *,
    lam: float,
    n_total: int,
    workers: int,
    model_axis: Optional[str] = None,
    model_lanes: Optional[int] = None,
    interpret: Optional[bool] = None,
    dv_scale_mul: float = 1.0,
) -> tuple[Block, Array, Array, Array]:
    """Epoch over a *physically partitioned* workload (the distributed
    layout): partition != 'static' re-deals buckets across lanes, the
    visit order is a fresh per-worker shuffle.  Works with either
    collectives backend — this is the program the sim<->mesh
    equivalence test runs on both.  `model_axis` + `model_lanes` on a
    sparse block select the feature-sharded solver layout (the model
    axis carries v slices and joins the sync axes instead of the
    example axes — launch/glm.py wires both ends).  `dv_scale_mul` is
    the health guard's damping, as in `sim_epoch_dense`."""
    algo = spec.algo
    lam_n = lam * n_total
    sig = spec.sigma_prime(workers)
    solver = make_local_solver(
        algo.local_solver, obj, lam_n, sig, bucket=algo.bucket,
        sparse=isinstance(block, SparseBlock), model_axis=model_axis,
        model_lanes=model_lanes, interpret=interpret,
        source="resident shard arrays")
    dv_scale = (1.0 / workers if algo.aggregation == "averaging"
                else 1.0) * dv_scale_mul
    return run_epoch(
        coll, solver, algo, block, y, a, v, epoch,
        redeal=(algo.partition != "static"), visit_shuffle=True,
        dv_scale=dv_scale)


# ---------------------------------------------------------------------------
# Simulator entry points (global arrays, schedule-based partitioning)
# ---------------------------------------------------------------------------


def _sim_gather(plan, bucket: int, epoch):
    """(P, K, n_local) global example ids for this epoch's schedule."""
    sched = plan.schedule(epoch)                       # (P, K, per_lane)
    return (sched[..., None] * bucket
            + jnp.arange(bucket, dtype=jnp.int32)).reshape(
                plan.pods, plan.lanes, -1)


def _sim_coll(spec: EngineConfig) -> SimCollectives:
    dep = spec.deployment
    return SimCollectives(pods=dep.pods, lanes=dep.lanes,
                          deterministic=dep.deterministic,
                          compress_pod=dep.compress_pod)


def sim_epoch_dense(
    obj: Objective,
    X: Array,                  # (d, n) dense, global
    y: Array,
    alpha: Array,
    v: Array,
    lam: float,
    plan,                      # PartitionPlan
    bplan,                     # BucketPlan
    spec,                      # EngineConfig (or anything .to_engine())
    epoch,
    straggler_mask: Optional[Array] = None,
    *,
    dv_scale_mul: float = 1.0,
) -> tuple[Array, Array]:
    """One simulated epoch over P*K virtual workers (dense path).

    Partitioning comes from `plan.schedule` (static/dynamic/
    hierarchical/rotation/alltoall as index math on the global arrays);
    the engine then runs the exact same chunk/sync/pod-reduce program
    as the distributed launcher.

    Cost note: the epoch's schedule is gathered once up front, so the
    jitted epoch holds one extra X-sized permuted copy (the distributed
    path never does this — its layout is physical).  At simulator
    scale (CPU, n <= a few hundred k) this is the right trade for
    sharing the engine's chunk loop verbatim.
    """
    spec = as_engine_config(spec)
    d, n = X.shape
    B = bplan.bucket
    ex = _sim_gather(plan, B, epoch)                   # (P, K, n_local)
    Xl = jnp.transpose(X[:, ex], (1, 2, 0, 3))         # (P, K, d, n_local)
    coll = _sim_coll(spec)
    W = plan.pods * plan.lanes
    solver = make_local_solver(
        spec.algo.local_solver, obj, lam * n, spec.sigma_prime(W),
        bucket=B)
    # dv_scale_mul < 1 is the health guard's "damp" remedy: CoCoA
    # partial aggregation (gamma) applied uniformly on top of the
    # averaging/adding choice
    dv_scale = (1.0 / W if spec.algo.aggregation == "averaging"
                else 1.0) * dv_scale_mul
    _, _, a_new, v_new = run_epoch(
        coll, solver, spec.algo, DenseBlock(Xl), y[ex], alpha[ex], v,
        epoch, straggler_mask=straggler_mask, redeal=False,
        visit_shuffle=False, dv_scale=dv_scale)
    return alpha.at[ex].set(a_new), v_new


def sim_epoch_sparse(
    obj: Objective,
    idx: Array,                # (n, nnz) int32, global
    val: Array,                # (n, nnz)
    y: Array,
    alpha: Array,
    v: Array,                  # (d,)
    lam: float,
    plan,
    bplan,
    spec,
    epoch,
    straggler_mask: Optional[Array] = None,
    *,
    dv_scale_mul: float = 1.0,
) -> tuple[Array, Array]:
    """Sparse-path simulated epoch (padded CSR)."""
    spec = as_engine_config(spec)
    n = y.shape[0]
    B = bplan.bucket
    ex = _sim_gather(plan, B, epoch)
    coll = _sim_coll(spec)
    W = plan.pods * plan.lanes
    solver = make_local_solver(
        spec.algo.local_solver, obj, lam * n, spec.sigma_prime(W),
        bucket=B, sparse=True)
    dv_scale = (1.0 / W if spec.algo.aggregation == "averaging"
                else 1.0) * dv_scale_mul
    _, _, a_new, v_new = run_epoch(
        coll, solver, spec.algo, SparseBlock(idx[ex], val[ex]), y[ex],
        alpha[ex], v, epoch, straggler_mask=straggler_mask, redeal=False,
        visit_shuffle=False, dv_scale=dv_scale)
    return alpha.at[ex].set(a_new), v_new


# ---------------------------------------------------------------------------
# Out-of-core streaming: ChunkFeed + the streamed chunk loop (DESIGN.md S9)
# ---------------------------------------------------------------------------


class ChunkFeed(Protocol):
    """Host-side supplier of worker-shaped example chunks.

    The engine asks for GLOBAL bucket ids laid out (*wshape, nb_chunk)
    and gets back device-resident (data, y) covering those buckets'
    examples in schedule order:

        dense:   data (*wshape, d, nb_chunk*B)
        sparse:  data = (idx, val), each (*wshape, nb_chunk*B, nnz)
        labels:  y (*wshape, nb_chunk*B)

    `fetch` is called one chunk ahead from a worker thread (double
    buffering), so implementations must tolerate concurrent reads.
    Implementations live in `repro.data.cache` (`TileFeed` over the
    mmap'd bucket-tile cache, `ArrayFeed` over resident arrays).

    Contract on sparse rows: no feature id may repeat with a NONZERO
    value within a row (the CSR invariant the sparse Pallas kernel's
    bitwise guarantee rests on, DESIGN.md S11 — sanitize with
    `data.formats.zero_duplicates` when building a custom feed; chunks
    reach the solver inside the jitted step, where values can no
    longer be checked).
    """
    n: int          # global example count (padded)
    d: int
    bucket: int
    sparse: bool

    def fetch(self, bids: np.ndarray): ...


def make_streamed_step(coll: Collectives, solver: LocalSolver,
                       algo: AlgoConfig, *, dv_scale: float = 1.0,
                       jit: bool = True):
    """One streamed chunk: gather alpha rows, run `_apply_chunk` (the
    SAME body as `run_epoch`'s resident loop), scatter alpha back.

    Built once per trainer so the jitted step compiles once.  alpha is
    deliberately NOT donated: a mid-epoch failure (feed I/O error,
    interrupt) must leave the caller's pre-epoch alpha buffer alive so
    training state stays recoverable — donation would delete it on
    accelerator backends.
    """

    def step(data, yc, cols, a, v_c):
        ac = a[cols]
        a_new, v_c = _apply_chunk(coll, solver, algo, data, yc, ac, v_c,
                                  dv_scale=dv_scale)
        return a.at[cols].set(a_new), v_c

    return jax.jit(step) if jit else step


def run_epoch_streamed(
    coll: Collectives,
    feed: ChunkFeed,
    step,                      # from make_streamed_step
    plan,                      # PartitionPlan (host-evaluated schedule)
    algo: AlgoConfig,
    alpha: Array,              # (n,) global dual, device-resident
    v: Array,                  # (d,) shared vector, device-resident
    epoch: int,
    journal=None,              # optional resilience.EpochJournal
    stats: Optional[dict] = None,   # out: ingest-overlap metrics
) -> tuple[Array, Array]:
    """One epoch where `run_epoch`'s chunked sub-epoch loop consumes
    host-resident chunks instead of a device-resident block.

    The schedule is the same pure function of (seed, epoch) the
    in-memory simulator uses (`plan.schedule`), evaluated on host; the
    per-chunk compute is `_apply_chunk` — so with
    `deterministic=True` this path is bitwise-identical to
    `sim_epoch_dense`/`sim_epoch_sparse` on the same data (pinned by
    tests/test_pipeline.py) while only ever holding `chunks`-th of X on
    device.  Chunk c+1's host gather + H2D overlaps chunk c's compute
    (double buffering via a one-slot prefetch thread).

    With a `journal` (resilience.EpochJournal) the loop becomes
    crash-safe: state is snapshotted at chunk boundaries, and a
    re-entered epoch resumes from the journaled chunk cursor — because
    the schedule is pure in (seed, epoch), the resumed epoch replays
    exactly the not-yet-applied chunks and finishes bitwise-identical
    to an uninterrupted run (tests/test_resilience.py).  Without one,
    the loop body adds two ``is None`` checks per chunk and nothing
    else — no host sync, no checksum, zero overhead.

    A ``stats`` dict collects ingest-overlap metrics for the epoch
    (mutated in place): ``epoch_s`` wall time, ``ingest_wait_s`` the
    time the chunk loop spent BLOCKED on the prefetch thread (host
    gather + H2D not hidden behind compute), and
    ``transfer_hidden_frac = 1 - ingest_wait_s/epoch_s`` — the fig4
    streamed-mesh arm's headline number.  Passing one adds a
    `block_until_ready` at epoch end (an epoch boundary sync the
    benchmark wants anyway); None keeps the hot loop sync-free.
    """
    B = feed.bucket
    per_lane = plan.per_lane
    if per_lane % algo.chunks:
        raise ValueError(f"chunks={algo.chunks} must divide per-lane "
                         f"bucket count {per_lane}")
    per_chunk = per_lane // algo.chunks
    ep = int(epoch)
    sched = np.asarray(plan.schedule(ep))           # (P, K, per_lane)

    def fetch(c):
        with obs.span("ingest.fetch", epoch=ep, chunk=c):
            obs.add("chunks")
            bids = sched[..., c * per_chunk:(c + 1) * per_chunk]
            cols = (bids[..., None] * B
                    + np.arange(B, dtype=np.int32)).reshape(
                        bids.shape[:-1] + (per_chunk * B,))
            data, yc = feed.fetch(bids)
            return jnp.asarray(cols), data, yc

    v = coll.pod_replicate(v)
    v_in = v
    start = 0
    if journal is not None:
        with obs.span("journal", epoch=ep):
            got = journal.load_inflight(ep, alpha, v, v_in)
        if got is not None:
            start, alpha, v, v_in = got
            alpha, v, v_in = (jnp.asarray(alpha), jnp.asarray(v),
                              jnp.asarray(v_in))
    t_start = time.perf_counter()
    wait_s = 0.0
    with ThreadPoolExecutor(max_workers=1) as ex:
        nxt = ex.submit(fetch, start)
        for c in range(start, algo.chunks):
            if journal is not None:
                with obs.span("journal", epoch=ep, chunk=c):
                    journal.pre_chunk(ep, c)
            t0 = time.perf_counter()
            with obs.span("ingest.wait", epoch=ep, chunk=c):
                cols, data, yc = nxt.result()
            dt = time.perf_counter() - t0
            wait_s += dt
            obs.add("ingest_wait_s", dt)
            if c + 1 < algo.chunks:
                nxt = ex.submit(fetch, c + 1)
            alpha, v = step(data, yc, cols, alpha, v)
            if journal is not None:
                with obs.span("journal", epoch=ep, chunk=c):
                    journal.post_chunk(ep, c, alpha, v, v_in, algo.chunks)
    v = coll.pod_reduce(v, v_in)
    if stats is not None:
        jax.block_until_ready((alpha, v))
        wall = time.perf_counter() - t_start
        stats.update(
            epoch_s=wall, ingest_wait_s=wait_s,
            chunks=algo.chunks - start,
            transfer_hidden_frac=(max(0.0, 1.0 - wait_s / wall)
                                  if wall > 0 else 0.0))
    return alpha, v


def make_streamed_epoch(obj: Objective, spec, plan, feed: ChunkFeed, *,
                        lam: float, jit_step: bool = True,
                        journal=None, damp: float = 1.0):
    """-> epoch_fn(alpha, v, epoch) for out-of-core training.

    The streamed twin of the jitted `sim_epoch_dense`/`sim_epoch_sparse`
    closure `GLMTrainer` builds: same solver, same sigma', same
    schedule, but examples arrive chunk-by-chunk through `feed`.
    ``journal`` threads an `EpochJournal` into the chunk loop (crash
    safety); ``damp`` is the health guard's aggressiveness multiplier
    on dv_scale (mirrors sim_epoch_*'s dv_scale_mul).
    """
    spec = as_engine_config(spec)
    coll = _sim_coll(spec)
    W = plan.pods * plan.lanes
    solver = make_local_solver(
        spec.algo.local_solver, obj, lam * feed.n, spec.sigma_prime(W),
        bucket=feed.bucket, sparse=feed.sparse,
        source=("tile cache" if getattr(feed, "cache", None) is not None
                else "array feed"))
    dv_scale = (1.0 / W if spec.algo.aggregation == "averaging"
                else 1.0) * damp
    step = make_streamed_step(coll, solver, spec.algo,
                              dv_scale=dv_scale, jit=jit_step)

    def epoch_fn(alpha, v, epoch):
        return run_epoch_streamed(coll, feed, step, plan, spec.algo,
                                  alpha, v, epoch, journal=journal)

    return epoch_fn


# ---------------------------------------------------------------------------
# Mesh streaming: per-host input pipeline for the real mesh (DESIGN.md S16)
# ---------------------------------------------------------------------------
#
# `run_epoch_streamed` above is deliberately backend-agnostic: it only
# needs a schedule, a feed, a jitted step, and pod_replicate/pod_reduce.
# The three classes below supply mesh-flavoured implementations of those
# seams so the SAME chunk loop (double buffering, journal hooks, stats)
# streams host-resident tiles onto a shard_map mesh:
#
#   MeshSchedule     — host mirror of the mesh's per-worker PRNG streams
#                      (re-deal + visit order), so the host knows which
#                      GLOBAL buckets each shard consumes each epoch.
#   MeshChunkFeed    — host gather + `device_put` with explicit
#                      NamedShardings (one transfer lands every shard's
#                      slice), optionally slice-compacted per model lane.
#   MeshStreamDriver — pod_replicate/pod_reduce over a pod-stacked v
#                      using real collectives inside shard_map.
#
# plus `make_mesh_streamed_step`, the mesh twin of `make_streamed_step`.


class MeshSchedule:
    """Host-side mirror of the mesh epoch's bucket schedule.

    The resident mesh path re-deals buckets ON DEVICE (`MeshCollectives.
    redeal`: per-worker shuffle + tiled all_to_all over 'data') and then
    visits them in a per-worker shuffled order.  To stream, the host
    must know which GLOBAL bucket ids land on which worker each epoch —
    so this class replays the exact same PRNG streams in numpy:

        worker_key = fold(fold(fold(PRNGKey(seed), epoch), pod), lane)
        re-deal perm <- fold(worker_key, 0);  visit <- fold(worker_key, 1)

    (threefry is bitwise-identical host/device, so the mirror is safe),
    applies the all_to_all index permutation to a persistent bucket
    LAYOUT — initialized contiguous, exactly how a flat global array
    shards under P(example_axes) — and composes re-deals epoch over
    epoch, because the physical layout persists across epochs on the
    resident path.  `schedule(e)` is therefore a pure function of
    (seed, e): re-entrant resume (EpochJournal) and the streamed loop
    replay the identical bucket order the resident mesh executes.

    `lane` is counted data-major over the example axes: for replicated
    model lanes (model carries examples) lane = data_idx * M + model_idx
    and the re-deal exchanges within each (pod, model) column over the
    D data lanes; for feature-sharded runs the model axis carries no
    examples and lane = data_idx.

    NOTE `core.partition.PartitionPlan` cannot be reused here: its
    "alltoall" schedule draws from a different key chain (fold(seed,
    round) + split), so it does NOT mirror the mesh re-deal.
    """

    def __init__(self, n_buckets: int, *, pods: int = 1, data: int = 1,
                 model: int = 1, model_in_lanes: bool = True,
                 seed: int = 0, redeal: bool = True,
                 redeal_frac: float = 1.0, visit_shuffle: bool = True):
        self.n_buckets = int(n_buckets)
        self.pods, self.data, self.model = int(pods), int(data), int(model)
        self.model_in_lanes = bool(model_in_lanes)
        self.lanes = self.data * self.model if model_in_lanes else self.data
        if self.n_buckets % (self.pods * self.lanes):
            raise ValueError(
                f"n_buckets={n_buckets} not divisible by "
                f"{self.pods} pods x {self.lanes} lanes")
        self.seed = int(seed)
        self.redeal = bool(redeal)
        self.redeal_frac = float(redeal_frac)
        self.visit_shuffle = bool(visit_shuffle)
        self._base = np.arange(self.n_buckets, dtype=np.int32).reshape(
            self.pods, self.lanes, self.per_lane)
        self._layouts: list[np.ndarray] = []   # post-redeal, per epoch

    @property
    def per_lane(self) -> int:
        return self.n_buckets // (self.pods * self.lanes)

    def _keys(self, epoch: int):
        base = jax.random.fold_in(jax.random.PRNGKey(self.seed),
                                  np.int32(epoch))
        out = np.empty((self.pods, self.lanes), dtype=object)
        for p in range(self.pods):
            kp = jax.random.fold_in(base, np.int32(p))
            for ln in range(self.lanes):
                out[p, ln] = jax.random.fold_in(kp, np.int32(ln))
        return out

    def _perm(self, key, stream: int) -> np.ndarray:
        return np.asarray(jax.random.permutation(
            jax.random.fold_in(key, np.int32(stream)), self.per_lane))

    def _redeal(self, layout: np.ndarray, keys) -> np.ndarray:
        """One epoch's re-deal: mirror of MeshCollectives.redeal over
        the 'data' axis (shuffle, exchange the first `exch` buckets via
        the tiled all_to_all's index permutation)."""
        D = self.data
        nb = self.per_lane
        if D <= 1 or self.redeal_frac <= 0:
            return layout
        exch = max(int(nb * self.redeal_frac) // D * D, D)
        g = exch // D
        out = layout.copy()
        cols = self.model if self.model_in_lanes else 1
        for p in range(self.pods):
            for m in range(cols):
                lanes = [i * cols + m for i in range(D)]
                shuf = [out[p, ln][self._perm(keys[p, ln], 0)]
                        for ln in lanes]
                for j, lnj in enumerate(lanes):
                    head = np.concatenate(
                        [shuf[i][j * g:(j + 1) * g] for i in range(D)])
                    out[p, lnj] = np.concatenate([head, shuf[j][exch:]])
        return out

    def layout(self, epoch: int) -> np.ndarray:
        """(pods, lanes, per_lane) GLOBAL bucket ids each worker holds
        AFTER epoch `epoch`'s re-deal — i.e. the physical layout the
        resident mesh trains on during that epoch.  Tests use it to map
        physically-permuted resident state back to global order."""
        if not self.redeal:
            return self._base
        while len(self._layouts) <= epoch:
            r = len(self._layouts)
            prev = self._layouts[r - 1] if r else self._base
            self._layouts.append(self._redeal(prev, self._keys(r)))
        return self._layouts[epoch]

    def schedule(self, epoch) -> np.ndarray:
        """(pods, lanes, per_lane) bucket ids in VISIT order — the
        `plan.schedule` contract `run_epoch_streamed` consumes."""
        e = int(epoch)
        lay = self.layout(e)
        if not self.visit_shuffle:
            return lay.copy()
        keys = self._keys(e)
        out = np.empty_like(lay)
        for p in range(self.pods):
            for ln in range(self.lanes):
                out[p, ln] = lay[p, ln][self._perm(keys[p, ln], 1)]
        return out


class MeshChunkFeed:
    """`ChunkFeed` that lands each chunk SHARDED across a mesh.

    The host gathers a chunk's buckets (from a `TileCache`'s mmap'd
    tiles or a resident host-array feed), lays the examples out in
    worker-major order — the order a flat global array shards under
    P(example_axes) — and `jax.device_put`s ONCE per array with an
    explicit NamedSharding, so each device receives exactly its slice
    (the `MpDeviceLoader`+`ShardingSpec` idiom).  Called from
    `run_epoch_streamed`'s prefetch thread, this overlaps host gather +
    H2D of chunk c+1 with chunk c's on-mesh compute.

    Feature-sharded sparse runs (`model_lanes`/`d_loc` set) use the
    slice-compacted feed: the host compacts each row to each model
    lane's [m*d_loc, (m+1)*d_loc) feature slice via
    `TileCache.slice_gather(positions=True)` and ships (M, nc, w)
    idx/val/pos stacks sharded P('model', example_axes, ...) — each
    lane transfers only its own slice's nonzeros (w ≈ nnz/M), cutting
    per-lane H2D bytes ~M-fold; the mesh step reassembles exact full
    rows on device from one model-axis all_gather (see
    `make_mesh_streamed_step`).  The compaction width `w` is fixed at
    construction (one scan over the nonzeros, or pass `width=`) so the
    jitted step compiles once.

    ``verify=True`` crc-checks the touched tiles per fetch (same
    contract as `TileFeed`); `rebind(cache)` swaps in a rebuilt
    `TileCache` after quarantine, which `ResilientChunkFeed` uses so
    its corruption recovery preserves the mesh feed (sharding + width)
    instead of downgrading to a plain `TileFeed`.  ``bytes_h2d`` /
    ``fetch_s`` accumulate host-side transfer bytes and gather+put
    seconds for the fig4 overlap metrics.
    """

    def __init__(self, source, mesh, *, ex_axes: tuple[str, ...],
                 tp: bool = False, model_axis: Optional[str] = None,
                 model_lanes: Optional[int] = None,
                 d_loc: Optional[int] = None, verify: bool = False,
                 width: Optional[int] = None, nnz_multiple: int = 8):
        from jax.sharding import NamedSharding, PartitionSpec
        if hasattr(source, "gather_buckets"):        # TileCache
            self.cache, self.host = source, None
            m = source.meta
            self.n, self.d, self.bucket = m.n, m.d, m.bucket
            self.sparse = m.kind == "sparse"
            self.nnz = m.nnz if self.sparse else 0
        else:                                        # ArrayFeed-like
            self.cache, self.host = None, source
            self.n, self.d = source.n, source.d
            self.bucket, self.sparse = source.bucket, source.sparse
            self.nnz = int(source.idx.shape[-1]) if self.sparse else 0
        self.mesh = mesh
        self.ex_axes = tuple(ex_axes)
        self.verify = bool(verify)
        self.nnz_multiple = int(nnz_multiple)
        self.sliced = model_lanes is not None and self.sparse
        self.model_lanes = model_lanes
        self.d_loc = d_loc
        if self.sliced and d_loc is None:
            raise ValueError("slice-compacted feed needs d_loc")
        ex = PartitionSpec(self.ex_axes)
        self._y_s = NamedSharding(mesh, ex)
        if self.sliced:
            self._r_s = NamedSharding(
                mesh, PartitionSpec(model_axis, self.ex_axes, None))
            self.width = int(width) if width else self._scan_width()
        elif self.sparse:
            self._r_s = NamedSharding(
                mesh, PartitionSpec(self.ex_axes, None))
            self.width = None
        else:
            self._x_s = NamedSharding(
                mesh, PartitionSpec(model_axis if tp else None,
                                    self.ex_axes))
            self.width = None
        self.bytes_h2d = 0
        self.fetch_s = 0.0
        self.fetches = 0

    def rebind(self, cache) -> None:
        """Swap in a rebuilt TileCache (post-quarantine recovery)."""
        if self.cache is None:
            raise ValueError("rebind() only applies to cache-backed feeds")
        self.cache = cache

    def reset_stats(self) -> None:
        self.bytes_h2d, self.fetch_s, self.fetches = 0, 0.0, 0

    # -- host-side gather ------------------------------------------------
    def _host_gather(self, bf: np.ndarray):
        h = self.host
        B = self.bucket
        cols = (bf[:, None] * B
                + np.arange(B, dtype=np.int64)).reshape(-1)
        y = h.y[cols]
        if self.sparse:
            return (h.idx[cols], h.val[cols]), y
        return np.ascontiguousarray(h.X[:, cols]), y

    def _gather(self, bf: np.ndarray):
        if self.cache is not None:
            if self.verify:
                self.cache.verify_tiles(bf)
            return self.cache.gather_buckets(bf)
        return self._host_gather(bf)

    def _scan_width(self) -> int:
        """Fixed compaction width: max in-slice nonzero count over the
        WHOLE dataset, ceiled to the kernel lane multiple — so every
        chunk's compacted arrays share one static shape."""
        M, dl = self.model_lanes, self.d_loc
        best = 1
        if self.cache is not None:
            idx_f = self.cache._flat("idx")
            val_f = self.cache._flat("val")
            nnz = idx_f.shape[-1]
            per_tile = int(np.prod(idx_f.shape[1:]))
            step = max(1, (1 << 22) // max(per_tile, 1))
            for s in range(0, idx_f.shape[0], step):
                idx = np.asarray(idx_f[s:s + step]).reshape(-1, nnz)
                val = np.asarray(val_f[s:s + step]).reshape(-1, nnz)
                best = max(best, self._max_count(idx, val))
        else:
            best = self._max_count(self.host.idx, self.host.val)
        mult = self.nnz_multiple
        return min(-(-best // mult) * mult, max(self.nnz, 1))

    def _max_count(self, idx: np.ndarray, val: np.ndarray) -> int:
        # keep-mask matches compact_slice_rows(positions=True): real
        # entries plus explicit (idx!=0, val==0) zeros; (0, 0) padding
        # is reproduced by the reassembly base and needn't travel
        keep = (val != 0) | (idx != 0)
        lane = idx // self.d_loc
        best = 0
        for m in range(self.model_lanes):
            c = ((lane == m) & keep).sum(axis=-1)
            best = max(best, int(c.max(initial=0)))
        return best

    def _fetch_sliced(self, bf: np.ndarray):
        from repro.data.cache import compact_slice_rows
        M, dl = self.model_lanes, self.d_loc
        rows, y = self._gather(bf)
        idx, val = rows
        parts = []
        for m in range(M):
            if self.cache is not None:
                # the per-lane slice compaction IS slice_gather
                # (gathered= skips re-reading the tiles per lane)
                (gi, gv, gp), _ = self.cache.slice_gather(
                    bf, m * dl, (m + 1) * dl,
                    nnz_multiple=self.nnz_multiple, positions=True,
                    width=self.width, gathered=(rows, y))
            else:
                gi, gv, gp = compact_slice_rows(
                    idx, val, m * dl, (m + 1) * dl,
                    nnz_multiple=self.nnz_multiple, positions=True,
                    width=self.width)
            parts.append((gi, gv, gp))
        gi = np.stack([p[0] for p in parts])
        gv = np.stack([p[1] for p in parts])
        gp = np.stack([p[2] for p in parts])
        return (gi, gv, gp), y

    # -- the ChunkFeed entry point ---------------------------------------
    def fetch(self, bids: np.ndarray):
        t0 = time.perf_counter()
        bf = np.asarray(bids).reshape(-1)
        nbytes = 0
        if self.sliced:
            (gi, gv, gp), y = self._fetch_sliced(bf)
            nbytes += gi.nbytes + gv.nbytes + gp.nbytes
            data = (jax.device_put(gi, self._r_s),
                    jax.device_put(gv, self._r_s),
                    jax.device_put(gp, self._r_s))
        elif self.sparse:
            (idx, val), y = self._gather(bf)
            nbytes += idx.nbytes + val.nbytes
            data = (jax.device_put(idx, self._r_s),
                    jax.device_put(val, self._r_s))
        else:
            X, y = self._gather(bf)
            X = np.ascontiguousarray(X)
            nbytes += X.nbytes
            data = jax.device_put(X, self._x_s)
        y = np.ascontiguousarray(y)
        nbytes += y.nbytes
        yd = jax.device_put(y, self._y_s)
        self.bytes_h2d += nbytes
        obs.add("h2d_bytes", nbytes)
        self.fetch_s += time.perf_counter() - t0
        self.fetches += 1
        return data, yd

    def host_fetch(self, bids: np.ndarray):
        """Raw host-resident rows ``(data, y)`` for the requested
        buckets — uncompacted, no device_put.  Diagnostics (the
        Session's streamed gap/primal pass) use this instead of
        `fetch`, whose sliced-feed output is a per-lane compaction
        that plain margin kernels cannot consume."""
        return self._gather(np.asarray(bids).reshape(-1))


class MeshStreamDriver:
    """The `Collectives` sliver `run_epoch_streamed` needs, for a mesh.

    The streamed loop holds v pod-STACKED — (pods, d) with the leading
    axis sharded over 'pod' — so each pod accumulates its own replica
    across chunks exactly like `SimCollectives` does, and the final
    cross-pod combine runs the REAL `MeshCollectives.pod_reduce`
    (ordered gather-sum / int8 EF) inside a tiny shard_map program.
    """

    def __init__(self, mesh, coll: MeshCollectives, *, tp: bool = False):
        from jax.sharding import NamedSharding, PartitionSpec
        self.mesh, self.coll = mesh, coll
        self.pods = coll._pod_size()
        self._vdim = "model" if tp else None
        self._vp = NamedSharding(
            mesh, PartitionSpec(coll.pod_axis, self._vdim))
        self._v1 = NamedSharding(mesh, PartitionSpec(self._vdim))
        self._finish = None

    def pod_replicate(self, v: Array) -> Array:
        stacked = jnp.broadcast_to(v, (self.pods,) + v.shape)
        return jax.device_put(stacked, self._vp)

    def pod_reduce(self, v_pods: Array, v_in: Array) -> Array:
        if self.pods == 1:
            return v_pods[0]
        if self._finish is None:
            from jax.sharding import PartitionSpec
            coll = self.coll
            vp_spec = PartitionSpec(coll.pod_axis, self._vdim)

            def finish(vp, vi):
                return coll.pod_reduce(vp[0], vi[0])

            self._finish = jax.jit(shard_map(
                finish, self.mesh, in_specs=(vp_spec, vp_spec),
                out_specs=PartitionSpec(self._vdim)))
        return self._finish(v_pods, v_in)


def make_mesh_streamed_step(mesh, coll: MeshCollectives,
                            solver: LocalSolver, algo: AlgoConfig, *,
                            ex_axes: tuple[str, ...], sparse: bool,
                            tp: bool = False,
                            slice_lanes: Optional[int] = None,
                            model_axis: str = "model",
                            nnz: Optional[int] = None,
                            dv_scale: float = 1.0, jit: bool = True):
    """Mesh twin of `make_streamed_step`: same (data, yc, cols, alpha,
    v) -> (alpha, v) contract, but the chunk solve runs inside
    shard_map with `MeshCollectives`, on chunk arrays `MeshChunkFeed`
    landed pre-sharded.  alpha stays a replicated global (n,) array —
    the gather/scatter at chunk edges reshards rows to/from the
    example axes — and is NOT donated (same crash-recoverability
    contract as the sim step).

    Slice-compacted sparse chunks (`slice_lanes` = M model lanes) are
    reassembled to exact full rows on device before the solver: one
    model-axis all_gather of the (n_loc, w) idx/val/pos triple, then a
    positional scatter into a zeros-(n_loc, nnz) base.  The compaction
    keep-mask retains every entry that is not (idx=0, val=0) padding —
    which is exactly what the zeros base reproduces — and kept entries
    scatter to their original (row, position) slots, so the
    reconstruction is bitwise-exact (explicit zero-value entries from
    `zero_duplicates` included) and the downstream solver sees the
    identical arrays the resident path replicates.  The redundant
    bytes move from the host link onto ICI, where the sharded solver
    already pays a per-bucket working-set exchange (DESIGN.md S12).
    """
    from jax.sharding import NamedSharding, PartitionSpec

    e_spec = PartitionSpec(ex_axes)
    vdim = "model" if tp else None
    vp_spec = PartitionSpec(coll.pod_axis, vdim)
    if sparse:
        if slice_lanes:
            if nnz is None:
                raise ValueError("slice-compacted step needs nnz")
            r_spec = PartitionSpec(model_axis, ex_axes, None)
            data_specs = (r_spec, r_spec, r_spec)
        else:
            r_spec = PartitionSpec(ex_axes, None)
            data_specs = (r_spec, r_spec)
    else:
        data_specs = PartitionSpec(vdim, ex_axes)

    def body(data, yc, ac, vp):
        v_c = vp[0]
        if sparse and slice_lanes:
            ci, cv, cp = (t[0] for t in data)     # (n_loc, w) own slice
            # audit: collective-ok pure data movement (slice reassembly)
            gi = jax.lax.all_gather(ci, model_axis)
            gv = jax.lax.all_gather(cv, model_axis)  # audit: collective-ok
            gp = jax.lax.all_gather(cp, model_axis)  # audit: collective-ok
            n_loc = ci.shape[0]
            rows = jnp.broadcast_to(
                jnp.arange(n_loc, dtype=jnp.int32)[None, :, None],
                gp.shape)
            # pad slots carry pos=nnz -> dropped; kept (row, pos) pairs
            # are unique, so the scatter is order-independent
            full_i = jnp.zeros((n_loc, nnz), jnp.int32) \
                .at[rows, gp].set(gi, mode="drop")
            full_v = jnp.zeros((n_loc, nnz), jnp.float32) \
                .at[rows, gp].set(gv, mode="drop")
            data = (full_i, full_v)
        a_new, v_new = _apply_chunk(coll, solver, algo, data, yc, ac,
                                    v_c, dv_scale=dv_scale)
        return a_new, v_new[None]

    inner = shard_map(body, mesh,
                      in_specs=(data_specs, e_spec, e_spec, vp_spec),
                      out_specs=(e_spec, vp_spec))
    a_rep = NamedSharding(mesh, PartitionSpec(None))
    a_ex = NamedSharding(mesh, e_spec)

    def step(data, yc, cols, a, v_c):
        colsf = cols.reshape(-1)
        ac = jax.lax.with_sharding_constraint(a[colsf], a_ex)
        a_new, v_c = inner(data, yc, ac, v_c)
        a = jax.lax.with_sharding_constraint(
            a.at[colsf].set(a_new), a_rep)
        return a, v_c

    return jax.jit(step) if jit else step


# ---------------------------------------------------------------------------
# Simulator entry points (physically partitioned layout)
# ---------------------------------------------------------------------------


def sim_sharded_dense_epoch(obj, spec, X, y, a, v, epoch, *,
                            lam: float, n_total: int):
    """Distributed-layout epoch on stacked sim workers: X (P, K, d,
    n_local).  Mirrors make_dense_epoch exactly (same keys, same
    re-deal, same sums) — the sim side of the equivalence test.
    Bitwise-identical to the mesh when K mirrors its data axis
    (model=1 or feature-sharded; see module docstring)."""
    spec = as_engine_config(spec)
    coll = _sim_coll(spec)
    blk, y, a, v = sharded_epoch(
        obj, spec, coll, DenseBlock(X), y, a, v, epoch, lam=lam,
        n_total=n_total, workers=spec.workers)
    return blk.X, y, a, v


def sim_sharded_sparse_epoch(obj, spec, idx, val, y, a, v, epoch, *,
                             lam: float, n_total: int):
    """Sparse twin of sim_sharded_dense_epoch: idx/val (P, K, nl, nnz)."""
    spec = as_engine_config(spec)
    coll = _sim_coll(spec)
    blk, y, a, v = sharded_epoch(
        obj, spec, coll, SparseBlock(idx, val), y, a, v, epoch, lam=lam,
        n_total=n_total, workers=spec.workers)
    return blk.idx, blk.val, y, a, v
