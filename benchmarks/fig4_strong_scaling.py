"""Paper Fig 4: strong scaling of per-epoch time with lane count.

Plus the streamed-mesh ingest arm (DESIGN.md S16): resident mesh
training vs the same epochs streamed chunk-by-chunk through
`MeshChunkFeed`'s double-buffered device_put pipeline, measuring how
much of the host->device transfer hides behind compute
(``transfer_hidden_frac``) and what it costs in examples/s.
"""
from __future__ import annotations

import time

from repro.core import SolverConfig
from .common import DATASETS, emit, fit_timed, load

HEADER = ["bench", "dataset", "lanes", "s_per_epoch", "speedup_vs_1",
          "solver", "examples_per_s", "transfer_hidden_frac",
          "ingest_wait_s", "h2d_bytes_epoch", "h2d_bytes_model"]

STREAM_LANES = 2          # data lanes for the streamed arm's mesh
STREAM_N, STREAM_D = 4096, 64
STREAM_BUCKET, STREAM_CHUNKS = 8, 4


def _streamed_mesh_rows(quick: bool) -> list[dict]:
    """Resident vs streamed epochs on a (data=2) host mesh.

    Needs >= 2 devices (the bench-smoke CI job forces host devices);
    fewer skip the arm, same convention as fig6's sharded arm.  The
    streamed row reports both the MEASURED per-epoch ingest bytes
    (`MeshChunkFeed.bytes_h2d`) and the modeled quantity
    (`planner.streamed_transfer_bytes`, summed over workers) so CI
    can watch the model and the pipeline stay in agreement.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import planner
    from repro.data import make_dense_classification
    from repro.data.cache import ArrayFeed
    from repro.launch.glm import (GLMScale, make_dense_epoch,
                                  make_streamed_epoch_mesh)
    from repro.launch.mesh import make_host_mesh

    if jax.device_count() < STREAM_LANES:
        print(f"# fig4 streamed-mesh arm skipped: "
              f"{jax.device_count()} device(s) < {STREAM_LANES}")
        return []
    epochs = 2 if quick else 4
    n, d = (STREAM_N // 2, STREAM_D) if quick else (STREAM_N, STREAM_D)
    X, y = make_dense_classification(n=n, d=d, seed=4)
    X, y = np.asarray(X), np.asarray(y)
    scale = GLMScale("fig4-streamed", "dense", n=n, d=d,
                     bucket=STREAM_BUCKET, chunks=STREAM_CHUNKS,
                     lam=1e-3, compress_pod=False, deterministic=True,
                     local_solver="xla")
    mesh = make_host_mesh(pod=1, data=STREAM_LANES, model=1)
    rows = []

    # resident reference: whole dataset device-resident
    ep = jax.jit(make_dense_epoch(scale, mesh))
    st = (jnp.asarray(X), jnp.asarray(y), jnp.zeros(n), jnp.zeros(d))
    # warm epoch 0 and keep its OUTPUT state: epoch outputs carry the
    # mesh shardings, so timing from fresh inputs would pay one more
    # compile mid-loop; both arms then time epochs 1..epochs
    st = ep(*st, jnp.int32(0))
    jax.block_until_ready(st)
    t0 = time.perf_counter()
    for e in range(1, 1 + epochs):
        st = ep(*st, jnp.int32(e))
    jax.block_until_ready(st)
    wall = time.perf_counter() - t0
    rows.append(dict(bench="fig4", dataset="dense-streamed",
                     lanes=STREAM_LANES, solver="resident_mesh",
                     s_per_epoch=wall / epochs,
                     examples_per_s=n * epochs / wall))

    # streamed: chunks land through the double-buffered mesh feed
    feed = ArrayFeed(y, X=X, bucket=STREAM_BUCKET)
    stats: dict = {}
    epoch_fn = make_streamed_epoch_mesh(scale, mesh, feed, stats=stats)
    a, v = jnp.zeros(n), jnp.zeros(d)
    a, v = epoch_fn(a, v, 0)                               # warm the jit
    epoch_fn.feed.reset_stats()
    hidden, wait, t0 = [], 0.0, time.perf_counter()
    for e in range(1, 1 + epochs):
        a, v = epoch_fn(a, v, e)
        hidden.append(stats["transfer_hidden_frac"])
        wait += stats["ingest_wait_s"]
    wall = time.perf_counter() - t0
    rows.append(dict(
        bench="fig4", dataset="dense-streamed", lanes=STREAM_LANES,
        solver="streamed_mesh", s_per_epoch=wall / epochs,
        examples_per_s=n * epochs / wall,
        transfer_hidden_frac=float(np.mean(hidden)),
        ingest_wait_s=wait / epochs,
        h2d_bytes_epoch=epoch_fn.feed.bytes_h2d / epochs,
        h2d_bytes_model=planner.streamed_transfer_bytes(
            n, d, workers=STREAM_LANES) * STREAM_LANES))
    return rows


def run(quick: bool = False):
    rows = []
    names = ["higgs"] if quick else list(DATASETS)
    lanes = [1, 4, 16] if quick else [1, 2, 4, 8, 16, 32]
    for name in names:
        data = load(name)
        base = None
        for k in lanes:
            r = fit_timed(data, SolverConfig(
                pods=1, lanes=k, bucket=8, partition="dynamic"),
                max_epochs=4, tol=0.0)
            if base is None:
                base = r["s_per_epoch"]
            rows.append(dict(bench="fig4", dataset=name, lanes=k,
                             s_per_epoch=r["s_per_epoch"],
                             speedup_vs_1=base / r["s_per_epoch"]))
    rows += _streamed_mesh_rows(quick)
    return emit(rows, HEADER)


if __name__ == "__main__":
    run()
