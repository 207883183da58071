"""Real-dataset pipeline: ingestion -> tile cache -> streamed epochs.

Pins the PR-2 acceptance contract: svmlight/CSV round-trips are exact,
the bucket-tile cache is byte-stable across processes, and streamed-
from-cache training is bitwise-identical to in-memory training under
`deterministic=True` for a dense and a sparse registry dataset.
"""
import hashlib
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.core import EngineConfig, StreamedGLMTrainer, fit_dataset
from repro.data import (cache as tile_cache, formats, registry)

REPO = pathlib.Path(__file__).resolve().parents[1]

DET_CFG = EngineConfig.make(pods=2, lanes=2, bucket=8, chunks=2,
                            partition="hierarchical", deterministic=True)


# -- formats: svmlight / CSV ------------------------------------------------

def test_svmlight_parses_reference_text():
    text = ("# comment line\n"
            "+1 qid:3 1:0.5 4:-2 7:1e-3\n"
            "-1 2:1.25\n"
            "0.5   # empty row with float label\n")
    (idx, val), y, d = formats.parse_svmlight(text)
    np.testing.assert_array_equal(y, [1.0, -1.0, 0.5])
    assert d == 7                      # 1-based ids shifted down
    assert idx.shape == val.shape == (3, 3)
    np.testing.assert_array_equal(idx[0], [0, 3, 6])
    np.testing.assert_allclose(val[0], [0.5, -2.0, 1e-3])
    assert val[2].tolist() == [0.0, 0.0, 0.0]


def test_svmlight_errors():
    with pytest.raises(ValueError, match="bad label"):
        formats.parse_svmlight("notanumber 1:2\n")
    with pytest.raises(ValueError, match="feature id"):
        formats.parse_svmlight("1 0:2\n")       # 0 is invalid 1-based
    with pytest.raises(ValueError, match="exceeds nnz"):
        formats.parse_svmlight("1 1:1 2:2\n", nnz=1)


def test_csv_parses_header_and_shapes():
    text = "label,f1,f2\n1,0.5,-1\n-1,2,3\n"
    X, y = formats.parse_csv(text)
    assert X.shape == (2, 2)
    np.testing.assert_array_equal(y, [1.0, -1.0])
    np.testing.assert_array_equal(X[:, 1], [2.0, 3.0])


def test_svmlight_roundtrip_exact_seeded():
    rng = np.random.default_rng(0)
    n, nnz, d = 64, 5, 100
    idx = rng.integers(0, d, size=(n, nnz)).astype(np.int32)
    val = rng.standard_normal((n, nnz)).astype(np.float32)
    y = rng.standard_normal(n).astype(np.float32)
    text = formats.dump_svmlight(idx, val, y)
    (idx2, val2), y2, _ = formats.parse_svmlight(text, d=d, nnz=nnz)
    np.testing.assert_array_equal(y, y2)
    np.testing.assert_array_equal(idx, idx2)
    np.testing.assert_array_equal(val, val2)


def test_svmlight_roundtrip_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    f32 = st.floats(width=32, allow_nan=False, allow_infinity=False,
                    min_value=float(np.float32(1e-6)), max_value=1e6)

    @given(st.lists(st.lists(st.tuples(st.integers(0, 999), f32),
                             min_size=0, max_size=8,
                             unique_by=lambda t: t[0]),
                    min_size=1, max_size=16),
           st.lists(f32, min_size=16, max_size=16))
    @settings(max_examples=25, deadline=None)
    def roundtrip(rows, labels):
        n = len(rows)
        nnz = max(max((len(r) for r in rows), default=1), 1)
        idx = np.zeros((n, nnz), np.int32)
        val = np.zeros((n, nnz), np.float32)
        for i, r in enumerate(rows):
            for k, (j, x) in enumerate(r):
                idx[i, k], val[i, k] = j, x
        y = np.asarray(labels[:n], np.float32)
        text = formats.dump_svmlight(idx, val, y)
        (idx2, val2), y2, _ = formats.parse_svmlight(text, d=1000,
                                                     nnz=nnz)
        np.testing.assert_array_equal(val, val2)
        np.testing.assert_array_equal(np.where(val != 0, idx, 0), idx2)
        np.testing.assert_array_equal(y, y2)

    roundtrip()


def test_csv_roundtrip_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    f32 = st.floats(width=32, allow_nan=False, allow_infinity=False)

    @given(st.integers(1, 6), st.integers(1, 12), st.data())
    @settings(max_examples=25, deadline=None)
    def roundtrip(d, n, data):
        X = np.asarray(data.draw(st.lists(f32, min_size=d * n,
                                          max_size=d * n)),
                       np.float32).reshape(d, n)
        y = np.asarray(data.draw(st.lists(f32, min_size=n, max_size=n)),
                       np.float32)
        X2, y2 = formats.parse_csv(formats.dump_csv(X, y))
        np.testing.assert_array_equal(X, X2)
        np.testing.assert_array_equal(y, y2)

    roundtrip()


def test_to_dense_accumulates_duplicates():
    idx = np.asarray([[0, 0], [1, 2]], np.int32)
    val = np.asarray([[1.0, 2.0], [3.0, 4.0]], np.float32)
    X = formats.to_dense(idx, val, d=3)
    np.testing.assert_array_equal(X[:, 0], [3.0, 0.0, 0.0])
    np.testing.assert_array_equal(X[:, 1], [0.0, 3.0, 4.0])


# -- tile cache -------------------------------------------------------------

def test_cache_roundtrip_dense(tmp_path):
    rng = np.random.default_rng(1)
    d, n, B = 13, 96, 8                       # d deliberately un-padded
    X = rng.standard_normal((d, n)).astype(np.float32)
    y = rng.choice([-1.0, 1.0], n).astype(np.float32)
    tc = tile_cache.build_cache(tmp_path / "c", "t", X=X, y=y,
                                bucket=B, pods=2)
    assert tc.meta.d_pad == 16 and tc.meta.n == 96
    X2, y2 = tc.load_arrays()
    np.testing.assert_array_equal(X, X2)
    np.testing.assert_array_equal(y, y2)
    # tile gather == direct column slices for arbitrary bucket ids
    bids = np.asarray([[5, 0], [11, 3]])
    data, yg = tc.gather_buckets(bids)
    cols = (bids[..., None] * B + np.arange(B)).reshape(2, -1)
    np.testing.assert_array_equal(data, np.moveaxis(X[:, cols], 0, -2))
    np.testing.assert_array_equal(yg, y[cols])


def test_cache_roundtrip_sparse_with_padding(tmp_path):
    rng = np.random.default_rng(2)
    n, nnz, d, B = 50, 4, 32, 8               # n pads up to 64
    idx = rng.integers(0, d, (n, nnz)).astype(np.int32)
    val = rng.standard_normal((n, nnz)).astype(np.float32)
    y = rng.choice([-1.0, 1.0], n).astype(np.float32)
    tc = tile_cache.build_cache(tmp_path / "c", "t", idx=idx, val=val,
                                y=y, d=d, bucket=B, pods=2,
                                pad_multiple=64)
    assert tc.meta.n == 64 and tc.meta.n_examples == 50
    (idx2, val2), y2 = tc.load_arrays()
    np.testing.assert_array_equal(idx, idx2[:n])
    np.testing.assert_array_equal(val, val2[:n])
    np.testing.assert_array_equal(y, y2[:n])
    assert (val2[n:] == 0).all() and (y2[n:] == 1.0).all()


def test_cache_nnz_multiple_pads_rows_lane_aligned(tmp_path):
    """build_cache(..., nnz_multiple=8) pads odd row widths with inert
    idx=0/val=0 columns so tiles satisfy the sparse kernel's alignment
    (PR-4 satellite)."""
    rng = np.random.default_rng(7)
    n, nnz, d, B = 32, 5, 16, 8               # nnz 5 -> padded to 8
    idx = rng.integers(0, d, (n, nnz)).astype(np.int32)
    val = rng.standard_normal((n, nnz)).astype(np.float32)
    y = rng.choice([-1.0, 1.0], n).astype(np.float32)
    tc = tile_cache.build_cache(tmp_path / "c", "t", idx=idx, val=val,
                                y=y, d=d, bucket=B, nnz_multiple=8)
    assert tc.meta.nnz == 8
    (idx2, val2), y2 = tc.load_arrays()
    np.testing.assert_array_equal(idx2[:, :nnz], idx)
    np.testing.assert_array_equal(val2[:, :nnz], val)
    assert (idx2[:, nnz:] == 0).all() and (val2[:, nnz:] == 0).all()
    np.testing.assert_array_equal(y2, y)
    # already-aligned widths are untouched, and the knob keys the
    # materialize cache so aligned/unaligned builds coexist
    tc2 = tile_cache.build_cache(tmp_path / "c2", "t", idx=idx2,
                                 val=val2, y=y, d=d, bucket=B,
                                 nnz_multiple=8)
    assert tc2.meta.nnz == 8
    a = registry.materialize("synthetic-sparse", tmp_path, n=64, d=32)
    b = registry.materialize("synthetic-sparse", tmp_path, n=64, d=32,
                             nnz_multiple=16)
    assert a.path != b.path and b.meta.nnz == 16


def test_cache_slice_gather_compacts_feature_slice(tmp_path):
    """TileCache.slice_gather keeps only a [lo, hi) feature slice's
    nonzeros, in row order, rebased to slice-local ids and padded to
    the kernel lane multiple (DESIGN.md S12 streamed-shard building
    block)."""
    rng = np.random.default_rng(11)
    n, nnz, d, B = 32, 8, 40, 8
    idx = rng.integers(0, d, (n, nnz)).astype(np.int32)
    val = rng.standard_normal((n, nnz)).astype(np.float32)
    y = rng.choice([-1.0, 1.0], n).astype(np.float32)
    tc = tile_cache.build_cache(tmp_path / "c", "t", idx=idx, val=val,
                                y=y, d=d, bucket=B)
    lo, hi = 16, 32
    bids = np.asarray([2, 0, 3])
    (idx_s, val_s), y_s = tc.slice_gather(bids, lo, hi)
    (idx_g, val_g), y_g = tc.gather_buckets(bids)
    np.testing.assert_array_equal(y_s, y_g)
    assert idx_s.shape[-1] % 8 == 0
    for r in range(idx_g.shape[0]):
        own = [(int(i) - lo, float(v)) for i, v in
               zip(idx_g[r], val_g[r]) if lo <= i < hi and v != 0]
        got = [(int(i), float(v)) for i, v in
               zip(idx_s[r], val_s[r]) if v != 0]
        assert got == own                         # order-preserving
        assert (val_s[r, len(own):] == 0).all()   # inert right padding
    # the slice's dense reconstruction equals slicing the full rows
    Xf = formats.to_dense(idx_g, val_g, d)[lo:hi]
    Xs = formats.to_dense(idx_s, val_s, hi - lo)
    np.testing.assert_array_equal(Xf, Xs)
    # guards: sparse-only, sane bounds
    rngd = np.random.default_rng(12)
    Xd = rngd.standard_normal((8, 16)).astype(np.float32)
    yd = np.ones(16, np.float32)
    tcd = tile_cache.build_cache(tmp_path / "cd", "t", X=Xd, y=yd,
                                 bucket=8)
    with pytest.raises(ValueError, match="sparse-only"):
        tcd.slice_gather(bids, lo, hi)
    with pytest.raises(ValueError, match="feature slice"):
        tc.slice_gather(bids, 8, 8)


def test_raw_ingest_nnz_multiple_reaches_pallas(tmp_path):
    """The alignment error's suggested fix is reachable from the top:
    a raw svmlight ingest with an odd row width trains with
    local_solver='pallas' once fit_dataset passes nnz_multiple=8."""
    import warnings
    from repro.core import EngineConfig, fit_dataset

    rng = np.random.default_rng(9)
    n, nnz, d = 96, 5, 64                     # nnz=5: misaligned raw rows
    idx = rng.integers(0, d, (n, nnz)).astype(np.int32)
    val = rng.standard_normal((n, nnz)).astype(np.float32)
    val = formats.zero_duplicates(idx, val)
    y = rng.choice([-1.0, 1.0], n).astype(np.float32)
    raw_dir = tmp_path / "raw"
    raw_dir.mkdir()
    (raw_dir / "criteo-kaggle-sub.svm").write_text(
        formats.dump_svmlight(idx, val, y))
    kw = dict(cache_dir=tmp_path / "cache", data_dir=raw_dir,
              streamed=True, max_epochs=2, tol=0.0, nnz_multiple=8)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        outs = {}
        for solver in ("xla", "pallas"):
            cfg = EngineConfig.make(lanes=2, bucket=8, chunks=2,
                                    deterministic=True,
                                    local_solver=solver)
            res = fit_dataset("criteo-kaggle-sub", cfg=cfg, **kw)
            outs[solver] = (res.alpha, res.v)
    assert np.array_equal(outs["xla"][0], outs["pallas"][0])
    assert np.array_equal(outs["xla"][1], outs["pallas"][1])
    assert np.abs(outs["pallas"][1]).max() > 0


def test_cache_version_and_magic_guard(tmp_path):
    rng = np.random.default_rng(3)
    X = rng.standard_normal((4, 16)).astype(np.float32)
    y = np.ones(16, np.float32)
    tile_cache.build_cache(tmp_path / "c", "t", X=X, y=y, bucket=8)
    doc = json.loads((tmp_path / "c" / "meta.json").read_text())
    doc["version"] = 999
    (tmp_path / "c" / "meta.json").write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="version"):
        tile_cache.open_cache(tmp_path / "c")
    doc["magic"] = "nope"
    (tmp_path / "c" / "meta.json").write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="not a"):
        tile_cache.open_cache(tmp_path / "c")
    # crc verification catches bit flips
    tc = tile_cache.build_cache(tmp_path / "c2", "t", X=X, y=y, bucket=8)
    assert tile_cache.open_cache(tc.path, verify=True)
    data = bytearray((tc.path / "X.bin").read_bytes())
    data[3] ^= 0xFF
    (tc.path / "X.bin").write_bytes(bytes(data))
    with pytest.raises(ValueError, match="crc32"):
        tile_cache.open_cache(tc.path, verify=True)


def _cache_digest(path: pathlib.Path) -> dict:
    return {f.name: hashlib.sha256(f.read_bytes()).hexdigest()
            for f in sorted(path.iterdir())}


def test_cache_bit_stable_across_processes(tmp_path):
    """Two builds of the same registry dataset — one in a fresh
    process — produce byte-identical cache directories."""
    here = registry.materialize("synthetic-sparse", tmp_path / "a",
                                bucket=8, pods=2, n=256, d=64)
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from repro.data import registry\n"
        "registry.materialize('synthetic-sparse', %r, bucket=8, pods=2, "
        "n=256, d=64)\n"
        % (str(REPO / "src"), str(tmp_path / "b")))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=dict(os.environ), timeout=300)
    assert r.returncode == 0, r.stderr
    da = _cache_digest(here.path)
    db = _cache_digest(next((tmp_path / "b").iterdir()))
    assert da == db


# -- registry ---------------------------------------------------------------

def test_registry_specs_and_fallbacks():
    with pytest.raises(ValueError, match="unknown dataset"):
        registry.get_spec("nope")
    ds = registry.get_dataset("higgs", n=512)
    assert not ds.sparse and ds.X.shape == (28, 512)
    assert 0 < ds.scale < 1e-3
    # row width is the kernel-aligned 40 (criteo's real ~39 padded to a
    # multiple of 8 so local_solver="pallas" works out of the box)
    ds = registry.get_dataset("criteo-kaggle-sub", n=256, d=128)
    assert ds.sparse and ds.idx.shape == (256, 40)
    assert ds.provenance == "synthetic"


def test_registry_ingests_raw_svmlight_file(tmp_path):
    rng = np.random.default_rng(4)
    idx = rng.integers(0, 64, (32, 4)).astype(np.int32)
    val = rng.standard_normal((32, 4)).astype(np.float32)
    y = rng.choice([-1.0, 1.0], 32).astype(np.float32)
    (tmp_path / "criteo-kaggle-sub.svm").write_text(
        formats.dump_svmlight(idx, val, y))
    ds = registry.get_dataset("criteo-kaggle-sub", data_dir=tmp_path)
    assert ds.provenance.startswith("file:")
    np.testing.assert_array_equal(ds.val, val)
    np.testing.assert_array_equal(ds.y, y)


# -- streamed == in-memory (the acceptance pin) -----------------------------

@pytest.mark.parametrize("name", ["synthetic-dense", "synthetic-sparse"])
def test_streamed_matches_inmemory_bitwise(tmp_path, name):
    """Streamed-from-cache training is bitwise-identical to in-memory
    training under deterministic=True (dense + sparse registry data)."""
    kw = dict(cfg=DET_CFG, cache_dir=tmp_path, n=512, d=64,
              max_epochs=3, tol=0.0)
    mem = fit_dataset(name, streamed=False, **kw)
    st = fit_dataset(name, streamed=True, **kw)
    assert np.array_equal(mem.alpha, st.alpha)
    assert np.array_equal(mem.v, st.v)
    assert np.abs(st.v).max() > 0              # actually trained
    assert st.final_gap < 1.0


def test_streamed_feed_sources_agree(tmp_path):
    """TileFeed (mmap cache) and ArrayFeed (resident arrays) drive the
    streamed loop to identical results — cache exactness isolated from
    the chunk-loop contract."""
    from repro.core import engine
    from repro.core.objectives import LOGISTIC

    cache = registry.materialize("synthetic-dense", tmp_path, bucket=8,
                                 pods=2, n=256, d=32, pad_multiple=64)
    X, y = cache.load_arrays()
    feeds = [cache.feed(),
             tile_cache.ArrayFeed(y, X=X, bucket=8)]
    outs = []
    for feed in feeds:
        tr = StreamedGLMTrainer(cache, cfg=DET_CFG, lam=1e-2)
        ep = engine.make_streamed_epoch(LOGISTIC, DET_CFG, tr.plan,
                                        feed, lam=1e-2)
        a, v = tr.alpha, tr.v
        for e in range(2):
            a, v = ep(a, v, e)
        outs.append((np.asarray(a), np.asarray(v)))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_array_equal(outs[0][1], outs[1][1])


def test_streamed_trainer_guards_bucket_mismatch(tmp_path):
    cache = registry.materialize("synthetic-dense", tmp_path, bucket=8,
                                 n=256, d=32)
    bad = EngineConfig.make(bucket=16)
    with pytest.raises(ValueError, match="bucket"):
        StreamedGLMTrainer(cache, cfg=bad)


def test_streamed_gap_matches_inmemory_diagnostics(tmp_path):
    res, tr = fit_dataset("synthetic-dense", cfg=DET_CFG,
                          cache_dir=tmp_path, n=512, d=64, streamed=True,
                          max_epochs=3, tol=0.0, return_trainer=True)
    mem_res, mem_tr = fit_dataset("synthetic-dense", cfg=DET_CFG,
                                  cache_dir=tmp_path, n=512, d=64,
                                  streamed=False, max_epochs=3, tol=0.0,
                                  return_trainer=True)
    assert tr.gap() == pytest.approx(mem_tr.gap(), rel=1e-3, abs=1e-6)
    assert tr.primal() == pytest.approx(mem_tr.primal(), rel=1e-3)


# -- benchmark harness ------------------------------------------------------

def test_bench_run_writes_json_and_fails_loudly(tmp_path, monkeypatch,
                                                capsys):
    sys.path.insert(0, str(REPO))
    from benchmarks import run as bench_run

    class Boom:
        @staticmethod
        def run(quick=True):
            raise RuntimeError("figure exploded")

    class Fine:
        @staticmethod
        def run(quick=True):
            return [{"bench": "ok", "gap": 1e-4}]

    out = tmp_path / "BENCH_2.json"
    monkeypatch.setattr(bench_run, "BENCHES",
                        [("fine", Fine), ("boom", Boom)])
    rc = bench_run.main(["--json", str(out)])
    assert rc == 1                              # a raising figure fails CI
    doc = json.loads(out.read_text())
    assert doc["failed"] == ["boom"]
    assert doc["figures"]["fine"]["final_gap"] == pytest.approx(1e-4)
    assert doc["figures"]["boom"]["failed"] is True
    assert str(out) in capsys.readouterr().out  # path is printed

    monkeypatch.setattr(bench_run, "BENCHES", [("fine", Fine)])
    assert bench_run.main(["--json", str(out)]) == 0
