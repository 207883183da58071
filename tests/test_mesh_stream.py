"""Mesh input pipeline (DESIGN.md S16): streamed-from-host training on
a shard_map mesh is bitwise-identical to resident mesh training — and
to the sim streamed loop driven by the same `MeshSchedule` — under
`deterministic=True`, for dense and sparse, replicated and
feature-sharded (slice-compacted) routes.

The multi-device tests shell out with 8 forced host devices (repo
convention: only launch entrypoints force device counts); the
compaction unit tests run in-process.
"""
import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import engine
from repro.data.cache import compact_slice_rows

REPO = pathlib.Path(__file__).resolve().parents[1]


def _run(code: str, timeout=600):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=str(REPO / "src"))
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)


# -- bitwise pins: streamed-mesh == resident-mesh == sim-streamed -----------

def test_mesh_streamed_trio_bitwise_dense():
    """Dense replicated on a (data=8) mesh: the mesh-streamed epochs,
    the resident mesh epochs, and the SIM streamed loop driven by the
    same `MeshSchedule` all produce bitwise-identical (alpha, v)."""
    r = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import engine
        from repro.core.objectives import LOGISTIC
        from repro.launch.mesh import make_host_mesh
        from repro.launch.glm import (GLMScale, make_dense_epoch,
                                      make_streamed_epoch_mesh)
        from repro.data.cache import ArrayFeed

        K = 8; n, d, B = 1024, 64, 8
        scale = GLMScale("t", "dense", n=n, d=d, bucket=B, chunks=2,
                         deterministic=True, compress_pod=False,
                         local_solver="xla", lam=1e-3)
        mesh = make_host_mesh(pod=1, data=K, model=1)
        rng = np.random.default_rng(0)
        X = rng.normal(size=(d, n)).astype(np.float32)
        y = rng.choice([-1.0, 1.0], size=n).astype(np.float32)

        ep = jax.jit(make_dense_epoch(scale, mesh))
        Xr, yr = jnp.asarray(X), jnp.asarray(y)
        ar, vr = jnp.zeros(n), jnp.zeros(d)
        for e in range(2):
            Xr, yr, ar, vr = ep(Xr, yr, ar, vr, e)

        stats = {}
        epoch_m = make_streamed_epoch_mesh(
            scale, mesh, ArrayFeed(y, X=X, bucket=B), stats=stats)
        am, vm = jnp.zeros(n), jnp.zeros(d)
        for e in range(2):
            am, vm = epoch_m(am, vm, e)

        sched = engine.MeshSchedule(n // B, pods=1, data=K, model=1,
                                    seed=scale.seed)
        epoch_s = engine.make_streamed_epoch(
            LOGISTIC, scale.engine_config(mesh), sched,
            ArrayFeed(y, X=X, bucket=B), lam=scale.lam)
        als, vs = jnp.zeros(n), jnp.zeros(d)
        for e in range(2):
            als, vs = epoch_s(als, vs, e)

        assert np.array_equal(np.asarray(vm), np.asarray(vs))
        assert np.array_equal(np.asarray(am), np.asarray(als))
        lay = epoch_m.schedule.layout(1)   # resident layout, last epoch
        cols = (lay[..., None] * B
                + np.arange(B, dtype=np.int64)).reshape(-1)
        assert np.array_equal(np.asarray(vm), np.asarray(vr))
        assert np.array_equal(np.asarray(am)[cols],
                              np.asarray(ar).reshape(-1))
        assert np.abs(np.asarray(vm)).max() > 0       # actually trained
        assert stats["chunks"] == 2
        assert 0.0 <= stats["transfer_hidden_frac"] <= 1.0
        assert epoch_m.feed.bytes_h2d == 2 * (n * d * 4 + n * 4)
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_mesh_streamed_bitwise_sparse_replicated():
    """Sparse replicated rows (full idx/val per worker) stream bitwise
    against the resident sparse mesh epochs."""
    r = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_host_mesh
        from repro.launch.glm import (GLMScale, make_sparse_epoch,
                                      make_streamed_epoch_mesh)
        from repro.data.cache import ArrayFeed

        n, d, nnz, B = 1024, 256, 8, 8
        rng = np.random.default_rng(2)
        idx = np.stack([rng.choice(d, size=nnz, replace=False)
                        for _ in range(n)]).astype(np.int32)
        val = rng.normal(size=(n, nnz)).astype(np.float32)
        y = rng.choice([-1.0, 1.0], size=n).astype(np.float32)
        scale = GLMScale("t", "sparse", n=n, d=d, nnz=nnz, bucket=B,
                         chunks=2, deterministic=True,
                         compress_pod=False, local_solver="xla",
                         lam=1e-3, seed=2)
        mesh = make_host_mesh(pod=1, data=8, model=1)
        ep = jax.jit(make_sparse_epoch(scale, mesh))
        st = (jnp.asarray(idx), jnp.asarray(val), jnp.asarray(y),
              jnp.zeros(n), jnp.zeros(d))
        for e in range(2):
            st = ep(*st, e)
        ar, vr = st[3], st[4]

        epoch_m = make_streamed_epoch_mesh(
            scale, mesh, ArrayFeed(y, idx=idx, val=val, d=d, bucket=B))
        am, vm = jnp.zeros(n), jnp.zeros(d)
        for e in range(2):
            am, vm = epoch_m(am, vm, e)

        assert np.array_equal(np.asarray(vm), np.asarray(vr))
        lay = epoch_m.schedule.layout(1)
        cols = (lay[..., None] * B
                + np.arange(B, dtype=np.int64)).reshape(-1)
        assert np.array_equal(np.asarray(am)[cols],
                              np.asarray(ar).reshape(-1))
        assert np.abs(np.asarray(vm)).max() > 0
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_mesh_streamed_bitwise_sparse_sharded_slice_compacted():
    """Feature-sharded sparse on a (data=4, model=2) mesh: the feed
    routes through `TileCache.slice_gather` (per-lane slice-compacted
    idx/val/pos), the step reassembles exact rows on device, and the
    result is bitwise the resident sharded run.  Per-lane transfer
    bytes follow the rows*w*12 model exactly."""
    r = _run("""
        import tempfile
        import jax, jax.numpy as jnp, numpy as np
        from repro.data import registry
        from repro.launch.mesh import make_host_mesh
        from repro.launch.glm import (GLMScale, make_sparse_epoch,
                                      make_streamed_epoch_mesh)

        root = tempfile.mkdtemp()
        cache = registry.materialize("synthetic-sparse", root, bucket=8,
                                     pods=1, n=512, d=64,
                                     pad_multiple=256)
        m = cache.meta
        (idx, val), y = cache.load_arrays()
        scale = GLMScale("t", "sparse", n=m.n, d=m.d, nnz=m.nnz,
                         bucket=m.bucket, chunks=4, feature_shard=True,
                         deterministic=True, compress_pod=False,
                         local_solver="xla", lam=1e-3, seed=3)
        mesh = make_host_mesh(pod=1, data=4, model=2)
        ep = jax.jit(make_sparse_epoch(scale, mesh))
        st = (jnp.asarray(idx), jnp.asarray(val), jnp.asarray(y),
              jnp.zeros(m.n), jnp.zeros(m.d))
        for e in range(2):
            st = ep(*st, e)
        ar, vr = st[3], st[4]

        epoch_m = make_streamed_epoch_mesh(scale, mesh, cache)
        feed = epoch_m.feed
        assert feed.sliced and feed.cache is cache
        am, vm = jnp.zeros(m.n), jnp.zeros(m.d)
        for e in range(2):
            am, vm = epoch_m(am, vm, e)

        assert np.array_equal(np.asarray(vm), np.asarray(vr))
        B = m.bucket
        lay = epoch_m.schedule.layout(1)
        cols = (lay[..., None] * B
                + np.arange(B, dtype=np.int64)).reshape(-1)
        assert np.array_equal(np.asarray(am)[cols],
                              np.asarray(ar).reshape(-1))
        assert np.abs(np.asarray(vm)).max() > 0
        # per-lane slice-compacted bytes: each of the M model lanes
        # ships rows*w*12 (idx/val/pos) + the shared labels
        M, w = 2, feed.width
        assert feed.bytes_h2d == 2 * (M * m.n * w * 12 + m.n * 4)
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_mesh_streamed_bitwise_dense_tp_and_pods():
    """Dense TP (feature-sharded, model=2) and a 2-pod mesh with the
    int8 cross-pod reduce both stream bitwise vs resident."""
    r = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_host_mesh
        from repro.launch.glm import (GLMScale, make_dense_epoch,
                                      make_streamed_epoch_mesh)
        from repro.data.cache import ArrayFeed

        n, d, B = 1024, 64, 8
        rng = np.random.default_rng(4)
        X = rng.normal(size=(d, n)).astype(np.float32)
        y = rng.choice([-1.0, 1.0], size=n).astype(np.float32)

        for name, kw, mk in [
            ("tp", dict(feature_shard=True, compress_pod=False, seed=4),
             dict(pod=1, data=4, model=2)),
            ("pods", dict(compress_pod=True, seed=6),
             dict(pod=2, data=4, model=1)),
        ]:
            scale = GLMScale(name, "dense", n=n, d=d, bucket=B,
                             chunks=2, deterministic=True,
                             local_solver="xla", lam=1e-3, **kw)
            mesh = make_host_mesh(**mk)
            ep = jax.jit(make_dense_epoch(scale, mesh))
            st = (jnp.asarray(X), jnp.asarray(y), jnp.zeros(n),
                  jnp.zeros(d))
            for e in range(2):
                st = ep(*st, e)
            ar, vr = st[2], st[3]
            epoch_m = make_streamed_epoch_mesh(
                scale, mesh, ArrayFeed(y, X=X, bucket=B))
            am, vm = jnp.zeros(n), jnp.zeros(d)
            for e in range(2):
                am, vm = epoch_m(am, vm, e)
            assert np.array_equal(np.asarray(vm),
                                  np.asarray(vr).reshape(-1)), name
            lay = epoch_m.schedule.layout(1)
            cols = (lay[..., None] * B
                    + np.arange(B, dtype=np.int64)).reshape(-1)
            assert np.array_equal(np.asarray(am)[cols],
                                  np.asarray(ar).reshape(-1)), name
            assert np.abs(np.asarray(vm)).max() > 0, name
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_session_mesh_streamed():
    """`Session(..., streamed=True, mesh=...)` drives the mesh
    pipeline: reproducible bitwise across constructions, ingest stats
    + h2d counters populated; without a streamed source the same mesh=
    places the resident rows on the mesh instead."""
    r = _run("""
        import jax, numpy as np
        from repro.api.session import Session
        from repro.core.config import EngineConfig
        from repro.launch.mesh import make_host_mesh

        rng = np.random.default_rng(7)
        n, d, B = 512, 32, 8
        X = rng.normal(size=(d, n)).astype(np.float32)
        y = rng.choice([-1.0, 1.0], size=n).astype(np.float32)
        cfg = EngineConfig.make(pods=1, lanes=4, bucket=B, chunks=2,
                                partition="alltoall",
                                deterministic=True,
                                local_solver="xla", compress_pod=False)
        mesh = make_host_mesh(pod=1, data=4, model=1)
        runs = []
        for _ in range(2):
            s = Session((X, y), objective="logistic", lam=1e-3,
                        cfg=cfg, streamed=True, mesh=mesh)
            s.fit(max_epochs=3, tol=0)
            runs.append(s)
        a, b = runs
        assert np.array_equal(np.asarray(a.v), np.asarray(b.v))
        assert np.array_equal(np.asarray(a.alpha), np.asarray(b.alpha))
        assert a.stream_stats["chunks"] == 2
        assert a.mesh_feed.bytes_h2d > 0
        assert np.isfinite(a.gap())
        r = Session((X, y), objective="logistic", lam=1e-3, cfg=cfg,
                    mesh=mesh)
        assert r.mesh_feed is None and r._rid is not None
        r.fit(max_epochs=3, tol=0)
        assert np.array_equal(np.asarray(r.v), np.asarray(a.v))
        assert np.array_equal(np.asarray(r.alpha), np.asarray(a.alpha))
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


# -- slice compaction unit tests (no devices needed) ------------------------

def _reassemble(idx, pieces, nnz):
    """Scatter per-lane (idx, val, pos) compactions back into full
    rows — the numpy mirror of the step's on-device all_gather +
    positional scatter."""
    n = idx.shape[0]
    fi = np.zeros((n, nnz), np.int32)
    fv = np.zeros((n, nnz), np.float32)
    for ic, vc, pos in pieces:
        rows = np.broadcast_to(np.arange(n)[:, None], pos.shape)
        keep = pos < nnz                  # pos == nnz is the pad slot
        fi[rows[keep], pos[keep]] = ic[keep]
        fv[rows[keep], pos[keep]] = vc[keep]
    return fi, fv


def test_slice_compaction_positions_roundtrip():
    """compact_slice_rows(positions=True) pieces reassemble the exact
    original rows: global ids, explicit-zero values preserved, padding
    slots (idx=0, val=0) reproduced by the zeros base."""
    rng = np.random.default_rng(11)
    n, d, nnz, M = 64, 96, 12, 3
    idx = np.stack([rng.choice(d, size=nnz, replace=False)
                    for _ in range(n)]).astype(np.int32)
    val = rng.normal(size=(n, nnz)).astype(np.float32)
    val[rng.random((n, nnz)) < 0.2] = 0.0     # explicit zeros
    idx[:, -2:] = 0                           # padding tail
    val[:, -2:] = 0.0
    dl = d // M
    pieces = [compact_slice_rows(idx, val, m * dl, (m + 1) * dl,
                                 positions=True)
              for m in range(M)]
    fi, fv = _reassemble(idx, pieces, nnz)
    assert np.array_equal(fi, idx)
    assert np.array_equal(fv, val)


def test_slice_compaction_per_lane_bytes_and_width():
    """The per-lane compaction is the ~M-fold transfer saving: each
    lane's (idx, val, pos) triple is rows*w*12 bytes with w ~= nnz/M,
    vs rows*nnz*8 for full replicated rows; an undersized forced width
    raises instead of silently dropping nonzeros."""
    rng = np.random.default_rng(13)
    n, d, nnz, M = 128, 4096, 256, 8
    idx = np.stack([rng.choice(d, size=nnz, replace=False)
                    for _ in range(n)]).astype(np.int32)
    val = rng.normal(size=(n, nnz)).astype(np.float32)
    dl = d // M
    per_lane = []
    for m in range(M):
        ic, vc, pos = compact_slice_rows(idx, val, m * dl, (m + 1) * dl,
                                         positions=True)
        per_lane.append(ic.nbytes + vc.nbytes + pos.nbytes)
        assert ic.shape[1] <= compact_slice_rows(
            idx, val, m * dl, (m + 1) * dl, positions=True,
            width=ic.shape[1])[0].shape[1]
    full = n * nnz * 8
    # uniform ids: each slice holds ~nnz/M of the row, so per-lane
    # bytes land well under the replicated-row transfer
    assert max(per_lane) < full / 2
    with pytest.raises(ValueError):
        compact_slice_rows(idx, val, 0, dl, positions=True, width=1)


def test_mesh_schedule_pure_and_composed():
    """`MeshSchedule` is a pure function of (seed, epoch): independent
    instances agree, layouts compose re-deals epoch over epoch, and
    every epoch's schedule is a permutation of all buckets."""
    a = engine.MeshSchedule(64, pods=2, data=2, model=2, seed=9)
    b = engine.MeshSchedule(64, pods=2, data=2, model=2, seed=9)
    s3 = a.schedule(3)                  # builds layouts 0..3 in order
    assert np.array_equal(s3, b.schedule(3))
    assert np.array_equal(a.layout(2), b.layout(2))
    for e in range(4):
        assert np.array_equal(np.sort(a.schedule(e), axis=None),
                              np.arange(64))
    # static mode: layout never moves, visit order still shuffles
    st = engine.MeshSchedule(64, pods=2, data=2, model=2, seed=9,
                             redeal=False)
    assert np.array_equal(st.layout(3), st.layout(0))
    assert not np.array_equal(st.schedule(1), st.schedule(2))
