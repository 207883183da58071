"""The program's spans and counters (`repro.obs`, docs/tracing.md): the
counts a `fit` leaves, the span tree a profiler trace holds, and the
span a compile is put down to."""
import glob
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.api import EarlyStopping, Session
from repro.core import EngineConfig
from repro.data import make_dense_classification, make_sparse_classification

CFG = EngineConfig.make(pods=1, lanes=2, bucket=8, chunks=2,
                        partition="hierarchical", deterministic=True)


def _session(kind):
    if kind == "sparse":
        (idx, val), y, d = make_sparse_classification(n=256, d=64, nnz=8,
                                                      seed=0)
        return Session(((idx, val), y), d=d, lam=1e-2, cfg=CFG)
    X, y = make_dense_classification(n=256, d=16, seed=0)
    return Session((np.asarray(X), np.asarray(y)), lam=1e-2, cfg=CFG,
                   streamed=kind == "streamed")


# Reads per gap check: the dense certificate is one read; the sparse one
# reads primal and dual; the streamed pass reads alpha, one loss and one
# conjugate sum per group of buckets (one group at this size) and the
# regulariser.  Each epoch adds rel_change and fit's vmax; a fit adds
# two result copies.
@pytest.mark.parametrize("kind,reads_per_gap", [
    ("dense", 1), ("sparse", 2), ("streamed", 4)])
def test_fit_counts_epochs_gap_checks_and_reads(kind, reads_per_gap):
    ses = _session(kind)
    obs.reset()
    res = ses.fit(max_epochs=3, tol=0.0,
                  callbacks=[EarlyStopping(monitor="gap", threshold=0.0)])
    got = obs.counters()
    assert res.epochs == got["epochs"] == 3
    assert got["gap_checks"] == 3
    assert got["host_reads"] == 3 * (2 + reads_per_gap) + 2
    # four bytes a scalar, n * 4 for each of the streamed pass's alpha
    # copies, and the result's v and alpha
    alphas = 3 if kind == "streamed" else 0
    scalars = 3 * (2 + reads_per_gap) - alphas
    assert got["host_read_bytes"] == 4 * (
        scalars + alphas * ses.n + ses.d + ses.n)
    if kind == "streamed":
        assert got["chunks"] == 3 * CFG.algo.chunks
        # every row and label, once an epoch and once a gap pass
        assert got["h2d_bytes"] == 2 * 3 * ses.n * (ses.d + 1) * 4
        assert got["ingest_wait_s"] >= 0
    else:
        assert "chunks" not in got and "h2d_bytes" not in got


def test_read_returns_host_values_and_counts_bytes():
    obs.reset()
    assert obs.read("x", jnp.float32(2.5)) == 2.5
    out = obs.read("x", lambda: jnp.arange(6, dtype=jnp.float32))
    assert isinstance(out, np.ndarray) and out.tolist() == list(range(6))
    got = obs.counters()
    assert (got["host_reads"], got["host_read_bytes"]) == (2, 28)


def test_load_state_dict_counts_host_to_device_bytes():
    ses = _session("dense")
    obs.reset()
    ses.load_state_dict(ses.state_dict())
    assert obs.counters()["h2d_bytes"] == 4 * (ses.n + ses.d)


def _host_spans(logdir):
    from jax.profiler import ProfileData
    path = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                     line.name, dict(ev.stats))
                    for ev in line.events if ev.name.startswith("repro.")]
    return out


def _parent(spans, child):
    """The innermost span of the same thread that holds `child`."""
    holders = [s for s in spans if s is not child and s[3] == child[3]
               and s[0] <= child[0] and child[1] <= s[1]]
    return min(holders, key=lambda s: s[1] - s[0])[2] if holders else None


def test_profiler_trace_holds_the_span_tree(tmp_path):
    ses = _session("sparse")
    ses.fit(max_epochs=1, tol=0.0)        # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        ses.fit(max_epochs=2, tol=0.0, callbacks=[
            EarlyStopping(monitor="gap", threshold=0.0)])
    spans = _host_spans(tmp_path)
    names = [s[2] for s in spans]
    assert names.count("repro.fit") == 1
    assert names.count("repro.epoch") == 2 and names.count("repro.gap") == 2
    parents = {}
    for s in spans:
        parents.setdefault(s[2], set()).add(_parent(spans, s))
    assert parents["repro.epoch"] == {"repro.fit"}
    assert parents["repro.epoch.program"] == {"repro.epoch"}
    assert parents["repro.read.rel_change"] == {"repro.epoch"}
    assert parents["repro.read.vmax"] == {"repro.fit"}
    assert parents["repro.gap"] == {"repro.fit"}
    assert parents["repro.read.primal"] == {"repro.gap"}
    assert parents["repro.read.dual"] == {"repro.gap"}
    assert parents["repro.callbacks"] == {"repro.fit"}
    assert parents["repro.read.result"] == {"repro.fit"}
    epochs = sorted(s for s in spans if s[2] == "repro.epoch")
    assert [s[4]["step_num"] for s in epochs] == [1, 2]
    reads = sorted(s for s in spans if s[2] == "repro.read.rel_change")
    assert [s[4]["epoch"] for s in reads] == [1, 2]


def test_compiles_are_keyed_by_the_span_that_compiled():
    x = jnp.arange(3.0)
    obs.reset()
    with obs.span("probe"):
        jax.jit(lambda a: a * 3.0 + 1.0)(x)
        with obs.span("probe.inner"):
            jax.jit(lambda a: a * 5.0 - 2.0)(x)
        jax.jit(lambda a: a * 7.0 - 4.0)(x)
    got = obs.counters()
    assert got["compiles.repro.probe"] == 2
    assert got["compiles.repro.probe.inner"] == 1
    assert not any(k.startswith("compiles.") and "probe" not in k
                   for k in got), got


# -- a resident mesh: placement, re-deal, lane sums and the row order ------

MESH = """
    import glob, json, sys
    import jax, numpy as np
    from jax.profiler import ProfileData
    from repro import obs
    from repro.api import Session
    from repro.core import EngineConfig
    from repro.data import make_sparse_classification

    (idx, val), y, d = make_sparse_classification(n=1024, d=64, nnz=8,
                                                  seed=0)
    cfg = EngineConfig.make(lanes=4, bucket=8, chunks=2,
                            partition="alltoall")
    out = {}
    obs.reset()
    ses = Session(((idx, val), y), d=d, lam=1e-2, cfg=cfg)
    out["placed"] = obs.counters()
    ses.fit(max_epochs=1, tol=0.0)        # compile outside the trace
    ses.load_state_dict(ses.state_dict())
    obs.reset()
    logdir = sys.argv[1]
    with jax.profiler.trace(logdir):
        ses.fit(max_epochs=2, tol=0.0)
        ses.load_state_dict(ses.state_dict())
    out["fit"] = obs.counters()
    path = glob.glob(logdir + "/**/*.xplane.pb", recursive=True)[0]
    out["spans"] = [
        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, line.name)
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:") for line in plane.lines
        for ev in line.events if ev.name.startswith("repro.")]
    print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_obs(tmp_path_factory):
    repo = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(repo / "src"))
    logdir = str(tmp_path_factory.mktemp("mesh-trace"))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(MESH), logdir],
                       capture_output=True, text=True, env=env, timeout=600)
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    assert line, r.stdout + r.stderr
    return json.loads(line[-1][len("RESULT "):])


def test_mesh_counts_placement_redeal_bytes_and_lane_syncs(mesh_obs):
    # placement: idx and val (1024 x 8 x 4 B each), y and the row ids
    assert mesh_obs["placed"]["h2d_bytes"] == 2 * 1024 * 8 * 4 + 2 * 1024 * 4
    got = mesh_obs["fit"]
    # a lane holds 32 buckets of 8 rows and deals 3/4 of them away; a
    # row is 8 ids, 8 values, y, alpha and its row id: 76 B
    assert got["redeal_bytes"] == 2 * 4 * 32 * 8 * 76 * 3 // 4
    assert got["lane_syncs"] == 2 * 2          # chunks an epoch
    # reloaded row-ordered state: alpha (4 KB) and v (256 B)
    assert got["h2d_bytes"] == 1024 * 4 + 64 * 4
    assert not any(k.startswith("compiles.") for k in got), got


def test_mesh_spans_place_and_order(mesh_obs):
    spans = [tuple(s) for s in mesh_obs["spans"]]
    parents = {}
    for s in spans:
        parents.setdefault(s[2], []).append(_parent(spans, s))
    # the fit's alpha copy, then state_dict's, outside any other span
    assert parents["repro.mesh.order"] == ["repro.read.result", None]
    assert parents["repro.mesh.place"] == ["repro.state.load"]
