"""`Session`: the ONE owner of GLM solver state for every front end.

Every way of training the paper's solver — resident arrays, registry
dataset names, bucket-tile caches, out-of-core `ChunkFeed`s — used to
have its own driver (`GLMTrainer`, `StreamedGLMTrainer`, `fit_dataset`,
`cocoa.epoch_sim*`).  A `Session` subsumes them: it resolves the data
source once, owns the engine state (`alpha`, `v`, epoch counter, the
jitted epoch program), and exposes epoch-level control:

    s = Session((X, y), objective="logistic", lam=1e-3, cfg=cfg)
    s.epoch()                 # run exactly one epoch, get metrics back
    s.fit(until=10)           # train up to absolute epoch 10
    s.fit(max_epochs=5)       # ... or 5 more epochs from wherever we are

`fit` drives a callback protocol (`on_epoch_end(metrics) -> stop?`,
see `repro.api.callbacks`) used for early stopping, gap logging and
checkpoint hooks, and opens the `repro.*` spans and counters of
`repro.obs` at its boundaries (docs/tracing.md).  The sklearn-style
estimators in `repro.api.estimators` are thin facades over a Session;
the legacy trainers are deprecation shims over it (DESIGN.md S10).

Data sources accepted by the constructor, uniformly:

  * ``(X, y)``            dense arrays, engine layout ``X (d, n)``;
  * ``((idx, val), y)``   padded-CSR sparse (requires ``d=``);
  * ``"higgs"``           any `repro.data.registry` name (honouring
                          ``streamed=``/``cache_dir=``/``data_dir=``);
  * a `TileCache`         in-memory (``streamed=False``) or out-of-core;
  * a `ChunkFeed`         streamed training over any feed.

Examples are PADDED (x=0, y=+1 — inert, a zero row never moves v) up
to the multiple the chosen topology needs, so any sklearn-shaped n
trains without manual padding; ``n_examples`` records the true count.

Resident data goes on a device mesh where `DeploymentConfig.lanes_on`
says so (pods x lanes > 1 workers, features replicated, at least as
many devices), or where ``mesh=`` is given: each lane's rows on a
device of its own, re-dealt by all-to-all every epoch
(`launch.glm.make_sparse_epoch`/`make_dense_epoch`).  The rows carry
their row ids, so `alpha` leaves the session in the caller's row order
whatever the layout.  Everything else runs the simulator, its lanes
stacked on one device.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import engine, objectives
from repro.core.bucketing import BucketPlan, make_plan
from repro.core.config import EngineConfig, as_engine_config
from repro.core.objectives import Objective, get_objective
from repro.core.partition import PartitionPlan
from repro.core.trainer import FitResult

Array = jax.Array

__all__ = ["Session", "margins"]


def margins(v, data) -> jnp.ndarray:
    """Decision margins x_i^T v for engine-layout data.

    ``data`` is dense ``X (d, n)`` or a padded-CSR ``(idx, val)`` pair;
    returns ``(n,)``.  The one margin kernel shared by estimator
    ``decision_function``/``predict`` and the serving batch path.
    """
    if isinstance(data, (tuple, list)):
        idx, val = data
        return jnp.sum(jnp.asarray(v)[jnp.asarray(idx)]
                       * jnp.asarray(val), axis=1)
    return jnp.dot(jnp.asarray(data).T, jnp.asarray(v),
                   precision=jax.lax.Precision.HIGHEST)


class _MeshEpoch:
    """The shard_map epoch over rows resident on a mesh, called as
    `(alpha, v, epoch)` with alpha in the current layout.  Each call
    re-deals the rows, labels, row ids and alpha across the lanes and
    hands the new layout back to the session, so its next epoch, gap
    check and reads see rows and alpha that moved together."""

    def __init__(self, session, fn, *, redeal_bytes: int, lane_syncs: int):
        self._session = session
        self._fn = jax.jit(fn)
        self._redeal_bytes = redeal_bytes
        self._lane_syncs = lane_syncs

    def __call__(self, a, v, e):
        s = self._session
        *rows, a, v = self._fn(*s._layout(), a, v, e)
        s._set_layout(rows)
        obs.add("redeal_bytes", self._redeal_bytes)
        obs.add("lane_syncs", self._lane_syncs)
        return a, v


class _ResidentEpoch:
    """The jitted resident epoch `fn(alpha, v, epoch, *data)`, called as
    `(alpha, v, epoch)`.  The data rides in as arguments: arrays a jitted
    function closes over are embedded in its executable as constants,
    which at published sizes means hundreds of MB per program."""

    def __init__(self, fn, *data):
        self._fn = jax.jit(fn)
        self._data = data

    def __call__(self, a, v, e):
        return self._fn(a, v, e, *self._data)

    def lower(self, a, v, e):
        return self._fn.lower(a, v, e, *self._data)


def _pad_multiple(spec: EngineConfig, bucket: int) -> int:
    """Example-count multiple every partition mode divides: the same
    pods*lanes*lanes*chunks*bucket rule the tile cache builds with."""
    dep, algo = spec.deployment, spec.algo
    return dep.pods * dep.lanes * dep.lanes * algo.chunks * max(bucket, 1)


def _check_sparse_kernel_invariant(spec: EngineConfig, idx: np.ndarray,
                                   val: np.ndarray, d: int,
                                   bucket: int) -> None:
    """Ad-hoc sparse rows headed for the Pallas kernel must hold the
    CSR no-duplicate-nonzero invariant (DESIGN.md S11) — checked HERE,
    while the arrays are still concrete host arrays: inside the jitted
    epoch program they are tracers and `kernels.ops` cannot see the
    values.  Only enforced when the kernel will actually run them: the
    XLA scan accumulates duplicates fine, so "auto" off-TPU, explicit
    "xla", and backend-picked "auto" workloads the engine's misfit
    fallback routes to the scan anyway all keep accepting such rows.
    `bucket` must be the RESOLVED bucket (the one make_plan/the feed
    will run with), not spec.algo.bucket — the two differ when the
    Session bucket kwarg overrides the config.
    """
    kind = spec.algo.local_solver
    if kind not in ("pallas", "auto"):
        return
    if kind == "auto":
        kind, explicit = engine._resolve_auto()
        if kind != "pallas":
            return
        if not explicit:
            from repro.kernels import ops as kops
            B = max(bucket, 1)
            # n_local=B: divisibility is guaranteed by Session padding,
            # so only the shape/budget misfits matter here
            if kops.sparse_kernel_misfit(B, idx.shape[1], d, B):
                return   # engine falls back to the XLA scan per-workload
    from repro.data.formats import raise_on_duplicate_nonzeros
    raise_on_duplicate_nonzeros(idx, val, "ad-hoc sparse rows")


class Session:
    """Engine state + epoch control over one resolved data source.

    Resident rows go on a ("pod","data","model") mesh, one lane a
    device, where ``mesh=`` is given or `DeploymentConfig.lanes_on`
    holds for the backend's device count (the mesh is then built with
    `make_host_mesh(pod=pods, data=lanes)`); else the lanes are
    simulated on one device.  ``mesh=`` with a streamed source drives
    the mesh input pipeline instead.  A resident mesh takes no
    ``journal_dir`` or fault injector.
    """

    def __init__(self, data, y=None, *, objective: str | Objective | None
                 = None, lam: Optional[float] = None,
                 cfg: Any = None, d: Optional[int] = None,
                 bucket: Optional[int] = None, streamed: bool = False,
                 mesh=None,
                 cache_dir=None, data_dir=None, n: Optional[int] = None,
                 nnz_multiple: Optional[int] = None,
                 pad: bool = True, jit_step: bool = True,
                 health=None, journal_dir=None, journal_every: int = 1,
                 faults=None):
        self.spec = as_engine_config(cfg) if cfg is not None \
            else EngineConfig()
        self.cfg = cfg if cfg is not None else self.spec
        self.streamed = streamed
        # `mesh=` routes the streamed loop through the real-mesh input
        # pipeline (launch.glm.make_streamed_epoch_mesh / DESIGN.md
        # S16): chunks land pre-sharded via double-buffered device_put
        # instead of the stacked-sim layout.  `stream_stats` collects
        # the last epoch's ingest-overlap metrics on that path.  On
        # resident data it places the rows on the mesh (`_place`).
        self._mesh = mesh
        self._rid = None              # row ids of a resident mesh layout
        self.stream_stats: dict[str, float] = {}
        self.cache = None
        self.feed = None
        self.solver_plan = None       # set when "auto" routes via planner
        self.history: list[dict[str, float]] = []
        # resilience runtime (DESIGN.md S15) — all opt-in, all zero
        # overhead when left at the defaults.  `health` is a
        # HealthPolicy/HealthMonitor (or True for the defaults) that
        # fit() turns into a monitor callback; `journal_dir` enables
        # the crash-safe epoch journal; `faults` injects a deterministic
        # FaultInjector (tests), defaulting to $REPRO_FAULTS.
        from repro.resilience import EpochJournal, FaultInjector
        self._health = health
        self._damp = 1.0
        self._jit_step = jit_step
        self._faults = (faults if faults is not None
                        else FaultInjector.from_env())
        self._journal = (EpochJournal(journal_dir, every=journal_every,
                                      injector=self._faults)
                         if journal_dir is not None else None)

        # `Session((X, y))` / `Session(((idx, val), y))` sugar — only
        # when the second element is labels-shaped (1-D), so a
        # forgotten-y `Session((idx, val))` still raises clearly below
        if (y is None and isinstance(data, (tuple, list))
                and len(data) == 2 and not hasattr(data[0], "fetch")
                and np.ndim(data[1]) == 1):
            data, y = data

        if isinstance(data, str):
            self._init_from_registry(
                data, objective=objective, lam=lam, bucket=bucket,
                streamed=streamed, cache_dir=cache_dir,
                data_dir=data_dir, n=n, d=d,
                nnz_multiple=nnz_multiple, jit_step=jit_step)
        elif hasattr(data, "gather_buckets"):      # TileCache
            self._init_from_cache(data, objective=objective, lam=lam,
                                  streamed=streamed, jit_step=jit_step)
        elif hasattr(data, "fetch"):               # ChunkFeed
            self._init_from_feed(data, objective=objective, lam=lam,
                                 jit_step=jit_step)
        else:                                      # arrays
            if y is None:
                raise TypeError("array data requires labels: "
                                "Session((X, y)) or Session(X, y)")
            self._init_from_arrays(data, y, objective=objective, lam=lam,
                                   d=d, bucket=bucket, pad=pad,
                                   jit_step=jit_step)
        if self._journal is not None:
            # restart path: pick up the last committed epoch state, so
            # a re-constructed Session (new process after a crash)
            # continues exactly where the journal says — any mid-epoch
            # inflight record is consumed by the streamed loop itself
            got = self._journal.load_epoch(self.alpha, self.v)
            if got is not None:
                alpha, v, done = got
                self.alpha, self.v = jnp.asarray(alpha), jnp.asarray(v)
                self.epochs_done = done

    # -- construction: one per data source --------------------------------

    def _resolve_obj(self, objective, lam, default_obj="logistic",
                     default_lam=1e-3) -> None:
        objective = objective or default_obj
        self.obj = (objective if isinstance(objective, Objective)
                    else get_objective(objective))
        self.lam = float(default_lam if lam is None else lam)

    def _init_from_arrays(self, data, y, *, objective, lam, d, bucket,
                          pad, jit_step: bool = True,
                          trusted_rows: bool = False) -> None:
        """Resident-array setup.  When padding grows n -> n', lam is
        rescaled by n/n' so the padded objective

            (1/n') [sum_real loss + const] + (lam n / (2 n')) ||w||^2
          = (n/n') * [user objective] + const/n'

        keeps the USER's argmin exactly (and lam*n — the dual scaling —
        is unchanged); the inert rows' primal/dual terms cancel in the
        gap once their duals settle, so the certificate stays valid."""
        self._resolve_obj(objective, lam)
        sparse = isinstance(data, (tuple, list))
        y = np.asarray(y, np.float32)
        self.n_examples = y.shape[0]
        algo = self.spec.algo
        force = bucket if bucket is not None else (algo.bucket or None)
        B = force if force else 1
        # local_solver="auto" routes through the system-aware planner
        # (DESIGN.md S13).  Under the default $REPRO_PLAN=on|off the
        # geometry below stays bitwise today's static resolution (the
        # plan only records the route); $REPRO_PLAN=search|probe lets
        # the planner pick bucket/chunks when the caller left them at
        # the defaults (bucket kwarg unset and algo.bucket <= 1).
        self.solver_plan = None
        from repro.core import planner
        if (algo.local_solver == "auto" and (not sparse or d is not None)
                and planner.plan_mode() != "off"):
            open_geom = ((bucket is None and (algo.bucket or 1) == 1)
                         and planner.plan_mode() in ("search", "probe"))
            sig = planner.WorkloadSignature(
                n=int(y.shape[0]),
                d=int(d) if sparse else int(np.shape(data)[0]),
                nnz=int(np.shape(data[0])[1]) if sparse else 0,
                sparse=sparse)
            self.solver_plan = planner.resolve_plan(
                sig, planner.Topology.detect(self.spec),
                bucket=None if open_geom else B,
                chunks=None if open_geom else algo.chunks)
            if open_geom:
                force = B = self.solver_plan.bucket
                if self.solver_plan.chunks != algo.chunks:
                    algo = dataclasses.replace(
                        algo, chunks=self.solver_plan.chunks)
                    self.spec = dataclasses.replace(self.spec, algo=algo)
        idx = val = X = None
        if sparse:
            idx = np.asarray(data[0], np.int32)
            val = np.asarray(data[1], np.float32)
            if d is None:
                raise ValueError("sparse array data requires d")
            if not trusted_rows:
                # B is the resolved bucket make_plan/ArrayFeed run with
                _check_sparse_kernel_invariant(self.spec, idx, val,
                                               int(d), B)
            if pad:
                from repro.data.cache import pad_examples
                y, _, idx, val = pad_examples(
                    y, _pad_multiple(self.spec, B), idx=idx, val=val)
            self.n, self.d = int(y.shape[0]), int(d)
        else:
            X = np.asarray(data, np.float32)
            self.d = int(X.shape[0])
            if pad:
                from repro.data.cache import pad_examples
                y, X, _, _ = pad_examples(
                    y, _pad_multiple(self.spec, B), X=X)
            self.n = int(y.shape[0])
        if self.n > self.n_examples:
            self.lam *= self.n_examples / self.n

        if self.streamed:
            # arrays + streamed=True: drive the out-of-core loop over an
            # ArrayFeed built from the HOST arrays — nothing
            # example-sized goes device-resident (only alpha/v do)
            from repro.data.cache import ArrayFeed
            if sparse:
                feed = ArrayFeed(y, idx=idx, val=val, d=self.d, bucket=B)
            else:
                feed = ArrayFeed(y, X=X, bucket=B)
            self._init_from_feed(feed, objective=self.obj, lam=self.lam,
                                 jit_step=jit_step, rows_checked=True,
                                 lam_scaled=True)
            return

        self.sparse = sparse
        dep = self.spec.deployment
        self.bplan = make_plan(self.n, self.d, force=force or 1)
        if self.bplan.bucket != algo.bucket:
            # keep the plan's bucket authoritative (run_epoch chunks by
            # algo.bucket; single source of truth)
            algo = dataclasses.replace(algo, bucket=self.bplan.bucket)
            self.spec = dataclasses.replace(self.spec, algo=algo)
        self.plan = PartitionPlan(
            n_buckets=self.bplan.n_buckets, pods=dep.pods,
            lanes=dep.lanes, mode=algo.partition, seed=algo.seed,
            redeal_frac=algo.redeal_frac)
        block = (idx, val) if sparse else (X,)
        if self._mesh is None and dep.lanes_on(jax.device_count()):
            from repro.launch.mesh import make_host_mesh
            self._mesh = make_host_mesh(pod=dep.pods, data=dep.lanes)
        if self._mesh is not None:
            self._place(block, y)
        else:
            self._set_layout([jnp.asarray(x) for x in block + (y,)])
            self._init_state()
        self._rebuild_epoch_fn()

    def _place(self, block, y) -> None:
        """Shard the rows, labels, their row ids and alpha over the
        mesh's example axes, v replicated (`glm.resident_shardings`)."""
        from repro.launch import glm
        if self._journal is not None or self._faults is not None:
            raise ValueError(
                "a resident mesh re-deals rows every epoch, and the "
                "journal and fault injector keep state in a fixed row "
                "order: drop journal_dir/faults ($REPRO_FAULTS), or pass "
                "streamed=True")
        scale = glm.scale_for_estimator(
            self, **({"nnz": int(block[0].shape[1])} if self.sparse
                     else {}))
        workers = glm._worker_count(self._mesh, scale)
        if workers != self.spec.workers:
            raise ValueError(
                f"the mesh {dict(self._mesh.shape)} has {workers} "
                f"workers, the config pods x lanes = {self.spec.workers}")
        rows_sh, ex_sh, v_sh = glm.resident_shardings(scale, self._mesh)
        host = block + (y, np.arange(self.n, dtype=np.int32))
        with obs.span("mesh.place"):
            obs.add("h2d_bytes", sum(x.nbytes for x in host))
            self._set_layout([jax.device_put(x, sh) for x, sh in zip(
                host, rows_sh + (ex_sh, ex_sh))])
        # alpha[i] belongs to row rid[i]: one gather puts row-ordered
        # alpha into the layout, one scatter takes it back
        self._to_layout = jax.jit(lambda a, rid: a[rid],
                                  out_shardings=ex_sh)
        self._to_rows = jax.jit(
            lambda a, rid: jnp.zeros_like(a).at[rid].set(
                a, unique_indices=True), out_shardings=ex_sh)
        self._v_sharding = v_sh
        self._alpha = jnp.zeros(self.n, jnp.float32, device=ex_sh)
        self.v = jnp.zeros(self.d, jnp.float32, device=v_sh)
        self.epochs_done = 0

    def _layout(self) -> tuple:
        """The resident arrays in their current order: the rows, y and,
        on a mesh, the row ids."""
        rows = (self.idx, self.val) if self.sparse else (self.X,)
        return rows + (self.y,) + (() if self._rid is None
                                   else (self._rid,))

    def _set_layout(self, arrays) -> None:
        if self.sparse:
            self.idx, self.val, self.y, *rid = arrays
        else:
            self.X, self.y, *rid = arrays
        if rid:
            self._rid, = rid

    @property
    def alpha(self):
        """The dual vector, in the caller's row order on every route."""
        if self._rid is None:
            return self._alpha
        with obs.span("mesh.order"):
            return self._to_rows(self._alpha, self._rid)

    @alpha.setter
    def alpha(self, a) -> None:
        self._alpha = a if self._rid is None else self._to_layout(
            np.asarray(a), self._rid)

    def _init_from_cache(self, cache, *, objective, lam, streamed,
                         jit_step) -> None:
        meta = cache.meta
        self._resolve_obj(objective, lam, default_obj=meta.objective)
        if meta.n > meta.n_examples:
            # cache tiles arrive PRE-padded (pad=False / feed below), so
            # `_init_from_arrays`' padded-objective lam rescale never
            # fires on this path — apply the same n_examples/n factor
            # here so the inert rows keep the user's argmin exactly
            # (see _init_from_arrays' docstring for the algebra)
            self.lam *= meta.n_examples / meta.n
        algo = self.spec.algo
        if algo.bucket not in (0, 1, meta.bucket):
            raise ValueError(
                f"cfg bucket={algo.bucket} != cache bucket={meta.bucket}; "
                f"rebuild the cache at the training bucket size")
        if not streamed:
            arrays, y = cache.load_arrays()
            # cache builds dedupe rows (CACHE_VERSION 2) — don't re-sort
            # the whole dataset at construction to re-prove it
            kw = dict(objective=self.obj, lam=self.lam,
                      bucket=meta.bucket, pad=False, trusted_rows=True)
            if meta.kind == "sparse":
                self._init_from_arrays(arrays, y, d=meta.d, **kw)
            else:
                self._init_from_arrays(arrays, y, d=None, **kw)
            self.cache = cache
            self.n_examples = meta.n_examples
            return
        self.cache = cache
        self.streamed = True
        self._init_from_feed(cache.feed(), objective=self.obj,
                             lam=self.lam, jit_step=jit_step,
                             rows_checked=True, lam_scaled=True)

    def _init_from_feed(self, feed, *, objective, lam, jit_step,
                        rows_checked: bool = False,
                        lam_scaled: bool = False) -> None:
        self._resolve_obj(objective, lam)
        self.feed = feed
        self.streamed = True
        self.sparse = bool(feed.sparse)
        self.n, self.d = int(feed.n), int(feed.d)
        if (not rows_checked and self.sparse
                and getattr(feed, "cache", None) is None):
            # a user-supplied feed: check its rows here if it exposes
            # them as concrete host arrays (ArrayFeed); opaque
            # ChunkFeeds are bound by the protocol's documented CSR
            # invariant instead (engine.ChunkFeed)
            fidx = getattr(feed, "idx", None)
            fval = getattr(feed, "val", None)
            if fidx is not None and fval is not None:
                _check_sparse_kernel_invariant(
                    self.spec, np.asarray(fidx), np.asarray(fval),
                    self.d, int(feed.bucket))
        src_cache = getattr(feed, "cache", None)
        if src_cache is not None:
            self.n_examples = src_cache.meta.n_examples
            if not lam_scaled and self.n > self.n_examples:
                # a cache-backed feed handed to Session directly:
                # same padded-objective lam rescale as _init_from_cache
                # (which passes lam_scaled=True to not apply it twice)
                self.lam *= self.n_examples / self.n
        elif not hasattr(self, "n_examples"):
            self.n_examples = self.n
        algo, dep = self.spec.algo, self.spec.deployment
        if algo.bucket not in (0, 1, feed.bucket):
            raise ValueError(
                f"cfg bucket={algo.bucket} != feed bucket={feed.bucket}")
        self.bplan = BucketPlan(n=self.n, bucket=feed.bucket,
                                n_buckets=self.n // feed.bucket)
        self.plan = PartitionPlan(
            n_buckets=self.bplan.n_buckets, pods=dep.pods,
            lanes=dep.lanes, mode=algo.partition, seed=algo.seed,
            redeal_frac=algo.redeal_frac)
        self._init_state()
        self._rebuild_epoch_fn()

    def _init_from_registry(self, name, *, objective, lam, bucket,
                            streamed, cache_dir, data_dir, n, d,
                            nnz_multiple=None, jit_step=True) -> None:
        from repro.data import registry

        spec = registry.get_spec(name)
        objective = objective or spec.objective
        lam = spec.lam if lam is None else lam
        algo, dep = self.spec.algo, self.spec.deployment
        B = bucket or max(algo.bucket, 1)
        if streamed or cache_dir is not None:
            # nnz_multiple is the user-facing end of the sparse-kernel
            # alignment contract: raw svmlight ingests with odd row
            # widths pass nnz_multiple=8 HERE (or via fit_dataset) and
            # the built tiles land lane-aligned (DESIGN.md S11)
            cache = registry.materialize(
                name, cache_dir, bucket=B, pods=dep.pods, n=n, d=d,
                pad_multiple=_pad_multiple(self.spec, B),
                nnz_multiple=nnz_multiple, data_dir=data_dir)
            self._init_from_cache(cache, objective=objective, lam=lam,
                                  streamed=streamed, jit_step=jit_step)
            return
        ds = registry.get_dataset(name, n=n, d=d, data_dir=data_dir)
        if ds.sparse:
            # registry rows are deduped at the source (synthetic
            # samplers run zero_duplicates; svmlight holds the
            # invariant by construction)
            self._init_from_arrays((ds.idx, ds.val), ds.y,
                                   objective=objective, lam=lam,
                                   d=ds.d, bucket=B, pad=True,
                                   trusted_rows=True)
        else:
            self._init_from_arrays(ds.X, ds.y, objective=objective,
                                   lam=lam, d=None, bucket=B, pad=True)

    def _init_state(self) -> None:
        if not hasattr(self, "n_examples"):
            self.n_examples = self.n
        self.alpha = jnp.zeros(self.n, jnp.float32)
        self.v = jnp.zeros(self.d, jnp.float32)
        self.epochs_done = 0

    def _rebuild_epoch_fn(self) -> None:
        """(Re)compile the epoch program from the current spec/damp —
        called at construction and by health remedies (solver reroute,
        damping) that change how an epoch runs."""
        if self._rid is not None:
            from repro.launch import glm
            scale = glm.scale_for_estimator(self)
            make = (glm.make_sparse_epoch if self.sparse
                    else glm.make_dense_epoch)
            self._epoch_fn = _MeshEpoch(
                self, make(scale, self._mesh, obj=self.obj, row_ids=True,
                           damp=self._damp),
                redeal_bytes=glm.redeal_bytes(scale, self._mesh,
                                              row_ids=True),
                lane_syncs=scale.chunks)
        elif self.feed is not None and self._mesh is not None:
            from repro.launch import glm
            dep = self.spec.deployment
            kw: dict[str, Any] = {}
            if self.sparse:
                kw["feature_shard"] = dep.feature_shard
                nnz = getattr(self.feed, "nnz", None)  # MeshChunkFeed
                if not nnz:
                    inner = getattr(self.feed, "feed", self.feed)
                    fidx = getattr(inner, "idx", None)
                    if fidx is not None:
                        nnz = int(np.shape(fidx)[-1])
                if nnz:
                    kw["nnz"] = int(nnz)
            scale = glm.scale_for_estimator(self, **kw)
            self._epoch_fn = glm.make_streamed_epoch_mesh(
                scale, self._mesh, self.feed, obj=self.obj,
                journal=self._journal, damp=self._damp,
                stats=self.stream_stats, jit_step=self._jit_step)
        elif self.feed is not None:
            self._epoch_fn = engine.make_streamed_epoch(
                self.obj, self.spec, self.plan, self.feed, lam=self.lam,
                jit_step=self._jit_step, journal=self._journal,
                damp=self._damp)
        elif self.sparse:
            self._epoch_fn = _ResidentEpoch(
                lambda a, v, e, idx, val, y: engine.sim_epoch_sparse(
                    self.obj, idx, val, y, a, v, self.lam,
                    self.plan, self.bplan, self.spec, e,
                    dv_scale_mul=self._damp), self.idx, self.val, self.y)
        else:
            self._epoch_fn = _ResidentEpoch(
                lambda a, v, e, X, y: engine.sim_epoch_dense(
                    self.obj, X, y, a, v, self.lam,
                    self.plan, self.bplan, self.spec, e,
                    dv_scale_mul=self._damp), self.X, self.y)

    def _switch_local_solver(self, kind: str) -> None:
        """Reroute the local solver (the health guard's pallas→xla
        fallback — `_auto_fallback`'s warn-and-reroute idiom, made
        stateful) and rebuild the epoch program."""
        algo = dataclasses.replace(self.spec.algo, local_solver=kind)
        self.spec = dataclasses.replace(self.spec, algo=algo)
        self._rebuild_epoch_fn()

    # -- epoch-level control ----------------------------------------------

    def epoch(self) -> dict[str, float]:
        """Run exactly one epoch; returns {'epoch', 'rel_change', 't'}.

        't' is this epoch's duration when called standalone; inside
        `fit` the same record's 't' is rewritten to the cumulative
        fit wall-clock (one shared record, also kept in `history`)."""
        t0 = time.perf_counter()
        e = self.epochs_done
        with obs.span("epoch", step_num=e):
            if self._faults is not None:
                # deterministic fault probes ($REPRO_FAULTS / tests):
                # epoch-boundary kill, kernel failure on pallas routes,
                # post-epoch NaN poisoning (the resident twin of nan-chunk)
                self._faults.maybe_kill(e)
                if self.spec.algo.local_solver != "xla":
                    self._faults.maybe_kernel_fail(e)
            v_prev = self.v
            with obs.span("epoch.program", epoch=e):
                self._alpha, self.v = self._epoch_fn(
                    self._alpha, self.v, jnp.int32(e))
            if self._faults is not None and self._faults.nan_epoch(e):
                self.v = self.v * jnp.float32(float("nan"))
            self.epochs_done += 1
            if self._journal is not None:
                with obs.span("journal", epoch=e):
                    self._journal.commit_epoch(self._alpha, self.v,
                                               self.epochs_done)
            rel = obs.read(
                "rel_change",
                lambda: jnp.linalg.norm(self.v - v_prev)
                / jnp.maximum(jnp.linalg.norm(self.v), 1e-30), epoch=e)
            obs.add("epochs")
        rec = {"epoch": self.epochs_done, "rel_change": rel,
               "t": time.perf_counter() - t0}
        self.history.append(rec)
        return rec

    @obs.span("fit")
    def fit(self, *, until: Optional[int] = None,
            max_epochs: Optional[int] = None, tol: float = 1e-3,
            gap_every: int = 0, callbacks: Sequence = (),
            verbose: bool = False, diverge_above: float = 1e8,
            health=None) -> FitResult:
        """Train to `until` (absolute epoch) or `max_epochs` more epochs.

        Stops early when the relative model change drops below `tol`
        (the paper's stopping rule), when the iterate diverges, or when
        any callback's `on_epoch_end(metrics)` returns truthy.
        Re-entrant: a second `fit` continues from the current state, and
        schedules are pure functions of (seed, epoch), so
        stop/checkpoint/resume reproduces an uninterrupted run bitwise.

        ``health`` (a `HealthPolicy`, `HealthMonitor`, or True for the
        defaults; falls back to the Session's ``health=`` kwarg)
        installs the numerical-health guard: instead of the built-in
        break on divergence, an unhealthy epoch (or one that raises)
        rolls back to the last healthy snapshot and is retried /
        remediated per the policy (repro.resilience.health).
        """
        if until is None:
            until = self.epochs_done + (100 if max_epochs is None
                                        else max_epochs)
        elif max_epochs is not None:
            raise TypeError("pass either until= or max_epochs=, not both")
        from repro.resilience import HealthMonitor, HealthPolicy
        cbs = list(callbacks)
        monitor = next((cb for cb in cbs
                        if isinstance(cb, HealthMonitor)), None)
        health = health if health is not None else self._health
        if monitor is None and health is not None:
            if isinstance(health, HealthMonitor):
                monitor = health
            elif isinstance(health, HealthPolicy):
                monitor = HealthMonitor(health)
            else:                      # health=True -> default policy
                monitor = HealthMonitor()
            # first in line: it must see (and repair) the state before
            # other callbacks consume the epoch record
            cbs.insert(0, monitor)
        for cb in cbs:
            bind = getattr(cb, "bind", None)
            if bind is not None:
                bind(self)
        needs_gap = any(getattr(cb, "needs_gap", False) for cb in cbs)

        history: list[dict[str, float]] = []
        t0 = time.perf_counter()
        converged = diverged = False
        while self.epochs_done < until:
            try:
                rec = self.epoch()
            except Exception as err:
                # Only a health monitor may absorb an epoch failure —
                # it rolls back and remediates, re-raising when the
                # policy is exhausted.  SimulatedCrash is a
                # BaseException precisely so it can never land here.
                if monitor is None:
                    raise
                monitor.on_epoch_error(err)
                continue
            # mutate the record in place so self.history and the
            # returned FitResult.history stay the SAME objects
            rec["t"] = time.perf_counter() - t0
            want_gap = needs_gap or (
                gap_every and self.epochs_done % gap_every == 0)
            e = self.epochs_done - 1
            vmax = obs.read("vmax", lambda: jnp.max(jnp.abs(self.v)),
                            epoch=e)
            if not np.isfinite(vmax) or vmax > diverge_above:
                if monitor is None:
                    diverged = True
                    history.append(rec)
                    break
                want_gap = False       # gap over non-finite v is noise
            if want_gap:
                rec["gap"] = self.gap()
            history.append(rec)
            if verbose:
                print(f"epoch {self.epochs_done:4d} "
                      f"rel={rec['rel_change']:.3e} "
                      + (f"gap={rec['gap']:.3e}" if "gap" in rec else ""))
            stop = False
            with obs.span("callbacks", epoch=e):
                for cb in cbs:
                    fn = getattr(cb, "on_epoch_end", cb)
                    stop = bool(fn(rec)) or stop
            if rec["rel_change"] < tol:
                converged = True
                break
            if stop:
                break
        if monitor is not None and monitor.gave_up:
            diverged = True
        if not history:
            # until <= epochs_done (e.g. a loaded estimator that already
            # used its budget): report the CURRENT state honestly rather
            # than an empty history with a nan gap
            history = [{"epoch": self.epochs_done, "rel_change": 0.0,
                        "t": 0.0, "gap": self.gap()}]
        elif "gap" not in history[-1]:
            history[-1]["gap"] = self.gap() if not diverged else float("inf")
        e = self.epochs_done - 1
        return FitResult(
            epochs=self.epochs_done, converged=converged,
            diverged=diverged, v=obs.read("result", self.v, epoch=e),
            alpha=obs.read("result", lambda: self.alpha, epoch=e),
            history=history, wall_time=time.perf_counter() - t0)

    # -- diagnostics -------------------------------------------------------

    @property
    def mesh_feed(self):
        """The `MeshChunkFeed` driving a mesh-streamed session (h2d
        byte/seconds counters live there); None off the mesh path."""
        if self._mesh is None:
            return None
        return getattr(self._epoch_fn, "feed", None)

    def _streamed_primal_dual(self, gbuckets: int = 256
                              ) -> tuple[float, float]:
        """One streaming pass over the feed/cache: primal + dual sums."""
        src = self.cache if self.cache is not None else self.feed
        nb = self.bplan.n_buckets
        B = self.bplan.bucket
        e = self.epochs_done - 1
        loss_sum = conj_sum = 0.0
        alpha = obs.read("dual", self.alpha, epoch=e)
        v = self.v
        for start in range(0, nb, gbuckets):
            bids = np.arange(start, min(start + gbuckets, nb))
            if self.cache is not None:
                data, yb = src.gather_buckets(bids)
            else:
                # mesh feeds (possibly under a ResilientChunkFeed, whose
                # inner feed `make_streamed_epoch_mesh` upgrades in
                # place) expose host_fetch: raw uncompacted rows — the
                # sliced per-lane compaction `fetch` ships is not
                # margin-kernel shaped
                hf = getattr(src, "host_fetch", None) or getattr(
                    getattr(src, "feed", None), "host_fetch", None)
                data, yb = hf(bids) if hf is not None else src.fetch(bids)
            yb = jnp.asarray(yb)
            m = margins(v, data)
            loss_sum += obs.read(
                "primal", lambda: jnp.sum(self.obj.loss(m, yb)), epoch=e)
            a = jnp.asarray(alpha[start * B:start * B + yb.shape[0]])
            conj_sum += obs.read(
                "dual", lambda: jnp.sum(self.obj.conj_neg(a, yb)), epoch=e)
        reg = 0.5 * self.lam * obs.read("primal", lambda: jnp.sum(v ** 2),
                                        epoch=e)
        primal = loss_sum / self.n + reg
        dual = -conj_sum / self.n - reg
        return primal, dual

    def primal(self) -> float:
        """Primal objective P(v) at the current shared vector."""
        if self.streamed:
            return self._streamed_primal_dual()[0]
        e = self.epochs_done - 1
        if self.sparse:
            m = margins(self.v, (self.idx, self.val))
            return obs.read(
                "primal", lambda: jnp.sum(self.obj.loss(m, self.y)) / self.n
                + 0.5 * self.lam * jnp.sum(self.v ** 2), epoch=e)
        return obs.read("primal", lambda: objectives.primal_value(
            self.obj, self.v, self.X, self.y, self.lam), epoch=e)

    def gap(self) -> float:
        """Duality gap P(v) - D(alpha) — the convergence certificate."""
        e = self.epochs_done - 1
        with obs.span("gap", epoch=e):
            obs.add("gap_checks")
            if self.streamed:
                p, dv = self._streamed_primal_dual()
                return p - dv
            # rows, labels and alpha share one layout: the sums below
            # hold on a re-dealt mesh layout as on the caller's order
            if self.sparse:
                dval = objectives.dual_value(self.obj, self._alpha, self.v,
                                             self.y, self.lam)
                return self.primal() - obs.read("dual", dval, epoch=e)
            return obs.read("gap", lambda: objectives.duality_gap(
                self.obj, self._alpha, self.v, self.X, self.y, self.lam),
                epoch=e)

    # -- checkpoint/restart ------------------------------------------------

    def state_dict(self) -> dict[str, Any]:
        """Training state (alpha, v, epoch) as host arrays for checkpointing."""
        return {"alpha": np.asarray(self.alpha), "v": np.asarray(self.v),
                "epoch": np.int64(self.epochs_done)}

    def load_state_dict(self, st: dict[str, Any]) -> None:
        """Restore training state produced by `state_dict`: alpha in
        row order, put into the current layout on a mesh."""
        with obs.span("state.load"):
            obs.add("h2d_bytes", sum(st[k].nbytes for k in ("alpha", "v")
                                     if isinstance(st[k], np.ndarray)))
            if self._rid is None:
                self.alpha = jnp.asarray(st["alpha"])
                self.v = jnp.asarray(st["v"])
            else:
                with obs.span("mesh.place"):
                    self.alpha = st["alpha"]
                    self.v = jax.device_put(st["v"], self._v_sharding)
        self.epochs_done = int(st["epoch"])

    def save(self, path, *, meta: Optional[dict] = None) -> None:
        """Atomic on-disk snapshot of the solver state (+ meta)."""
        from repro.checkpoint import save_tree
        save_tree(path, self.state_dict(),
                  meta=dict(meta or {}, epochs_done=self.epochs_done))

    def load(self, path) -> dict:
        """Restore solver state saved by `save`; returns the meta dict."""
        from repro.checkpoint import restore_tree
        st, meta = restore_tree(path, self.state_dict())
        self.load_state_dict(st)
        return meta
