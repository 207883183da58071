"""Versioned, memory-mappable bucket-tile cache (DESIGN.md S9).

Cold-start ingest (text parsing, padding, layout packing) is paid ONCE:
`build_cache` packs a dataset into the on-disk analogue of the engine's
VMEM tile — examples grouped into buckets of B, each bucket stored as
one contiguous (d_pad x B) tile (dense) or (B x nnz) idx/val tile pair
(sparse), bucket-major, pod-sharded on the leading axis:

    X.bin    (pods, nb_pod, d_pad, B)  float32     [dense]
    idx.bin  (pods, nb_pod, B, nnz)    int32       [sparse]
    val.bin  (pods, nb_pod, B, nnz)    float32     [sparse]
    y.bin    (pods, nb_pod, B)         float32
    meta.json  — magic/version, shapes, true example count, crc32s

Epoch start is then an mmap + gather: `TileCache.gather_buckets` fancy-
indexes the memmap with global bucket ids, touching only the tiles a
chunk visits, and `TileFeed` device-puts the result — the `ChunkFeed`
the engine's streamed loop consumes.  Bucket b lives at
``tiles[b // nb_pod, b % nb_pod]``, matching `PartitionPlan`'s static
pod ranges, so a pod's epoch reads only its own shard of the file.

Determinism: the writer is a pure function of the input arrays (fixed
dtypes, C order, sorted-key JSON, no timestamps), so two builds of the
same dataset are byte-identical across processes — pinned by
tests/test_pipeline.py.

Padding: n is padded up to a multiple of ``pods * bucket`` (or the
caller's stricter ``pad_multiple``) with x=0 / y=+1 examples.  A zero
example never moves the shared vector v (its margin and update are
identically zero-weighted), so training is unaffected; diagnostics over
the padded set count the pad examples' flat loss terms, which shrink
with 1/n and are recorded via ``n_examples``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import zlib

import numpy as np

__all__ = [
    "CACHE_MAGIC", "CACHE_VERSION", "CacheMeta", "TileCache",
    "TileCorruptionError",
    "ArrayFeed", "TileFeed", "build_cache", "compact_slice_rows",
    "open_cache", "pad_examples",
]

CACHE_MAGIC = "repro-tile-cache"
# v2: synthetic sparse rows are deduplicated (formats.zero_duplicates)
# and criteo sub rows are 40 wide — pre-PR4 caches hold different bytes
# (including duplicate-nonzero rows that break the sparse Pallas
# kernel's bitwise contract), so they must not be silently reused.
# v3: per-tile crc32 sidecar (tilecrc.bin) so corruption is localized
# to a bucket tile (TileCorruptionError carries tile id + byte offset,
# enabling quarantine + targeted rebuild — DESIGN.md S15), and
# meta.json is committed LAST and atomically, so an interrupted build
# can never pass validation.
CACHE_VERSION = 3

_SUBLANE = 8          # pad d to the VPU sublane multiple

_TILECRC_FILE = "tilecrc.bin"


class TileCorruptionError(ValueError):
    """A cache tile's bytes no longer match their recorded crc32.

    Carries enough to act on (quarantine the cache, rebuild the tile
    from source): ``path`` is the corrupt ``.bin`` file, ``array`` its
    logical name, ``tile`` the GLOBAL bucket id of the first bad tile
    (None when only the whole-array checksum is available), ``offset``
    the byte offset of that tile inside the file.  Raised by
    `open_cache(verify=True)`, `TileCache.verify_tiles`, and
    `TileFeed(verify=True)`; classified as non-transient (no retry —
    the bytes will not get better) by
    `repro.resilience.ResilientChunkFeed`, which quarantines and
    rebuilds instead.
    """

    def __init__(self, path, array: str, tile: int | None = None,
                 offset: int | None = None):
        self.path = pathlib.Path(path)
        self.array = array
        self.tile = tile
        self.offset = offset
        loc = (f" (tile {tile} at byte offset {offset})"
               if tile is not None else "")
        super().__init__(
            f"{self.path}: crc32 mismatch for array {array!r}{loc} — "
            f"cache is corrupt; quarantine and rebuild from source")


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def compact_slice_rows(idx: np.ndarray, val: np.ndarray, lo: int,
                       hi: int, *, nnz_multiple: int = 8,
                       positions: bool = False,
                       width: int | None = None):
    """Compact padded-CSR rows to the entries in feature slice [lo, hi).

    The host half of the slice-compacted streamed feed (DESIGN.md
    S12/S16), shared by `TileCache.slice_gather` and the mesh feed's
    array-backed path.  Entries are kept IN ROW ORDER (stable
    left-compaction — the kernels' bitwise contract depends on
    within-row summation order) and right-padded to a common width
    ``w``: the max kept count ceiled to ``nnz_multiple``, or exactly
    ``width`` when given (so streamed chunks share one static shape;
    raises if a row overflows it).

    Two modes:

      * ``positions=False`` (default): keep nonzeros with
        ``lo <= idx < hi``, REBASE ids to slice-local coordinates
        (idx - lo).  Returns ``(idx_loc, val_loc)`` — the sharded
        kernels' slice-local layout.
      * ``positions=True``: the transfer format for exact on-device
        row reassembly.  Keeps every in-slice entry that is not
        (idx=0, val=0) padding — including explicit zero-VALUE entries
        (`formats.zero_duplicates` products), which a reassembled row
        must reproduce — and returns ``(idx, val, pos)`` with GLOBAL
        ids plus each entry's original within-row position; pad slots
        carry the sentinel ``pos = nnz`` so a `mode="drop"` scatter
        into a zeros base rebuilds the original row bitwise.

    All outputs are (*lead, w): idx/pos int32, val float32.
    """
    if not 0 <= lo < hi:
        raise ValueError(f"bad feature slice [{lo}, {hi})")
    in_slice = (idx >= lo) & (idx < hi)
    own = in_slice & (((val != 0) | (idx != 0)) if positions
                      else (val != 0))
    # stable left-compaction: sort each row by (not owned) so owned
    # entries keep their relative order
    order = np.argsort(~own, axis=-1, kind="stable")
    idx_s = np.take_along_axis(idx, order, axis=-1)
    val_s = np.take_along_axis(val, order, axis=-1)
    own_s = np.take_along_axis(own, order, axis=-1)
    need = max(int(own.sum(axis=-1).max(initial=0)), 1)
    if width is None:
        w = _ceil_to(need, nnz_multiple)
    else:
        w = int(width)
        if need > w:
            raise ValueError(
                f"width={w} too narrow: a row holds {need} entries "
                f"in slice [{lo}, {hi})")
    nnz = idx.shape[-1]
    val_c = np.where(own_s, val_s, 0.0).astype(np.float32)
    if positions:
        idx_c = np.where(own_s, idx_s, 0).astype(np.int32)
        pos = np.where(own_s, order, nnz).astype(np.int32)
        outs = [idx_c, val_c, pos]
        fills = [0, 0.0, nnz]     # pad slots keep the drop sentinel
    else:
        idx_c = np.where(own_s, idx_s - lo, 0).astype(np.int32)
        outs = [idx_c, val_c]
        fills = [0, 0.0]
    if w > nnz:                   # raw caches with unaligned nnz
        pad = [(0, 0)] * (idx_c.ndim - 1) + [(0, w - nnz)]
        outs = [np.pad(o, pad, constant_values=f)
                for o, f in zip(outs, fills)]
    return tuple(np.ascontiguousarray(o[..., :w]) for o in outs)


@dataclasses.dataclass(frozen=True)
class CacheMeta:
    """Everything needed to mmap the arrays back + provenance."""
    name: str
    kind: str                  # dense | sparse
    n: int                     # padded example count (what training sees)
    n_examples: int            # true example count before padding
    d: int
    d_pad: int                 # dense tile row count (d rounded up)
    bucket: int
    pods: int
    nnz: int                   # sparse only; 0 for dense
    objective: str
    version: int = CACHE_VERSION
    magic: str = CACHE_MAGIC

    @property
    def n_buckets(self) -> int:
        return self.n // self.bucket

    @property
    def nb_pod(self) -> int:
        return self.n_buckets // self.pods

    def array_specs(self) -> dict[str, tuple[tuple[int, ...], str]]:
        """name -> (shape, dtype) of every .bin file."""
        P, nbp, B = self.pods, self.nb_pod, self.bucket
        if self.kind == "dense":
            arrs = {"X": ((P, nbp, self.d_pad, B), "float32")}
        else:
            arrs = {"idx": ((P, nbp, B, self.nnz), "int32"),
                    "val": ((P, nbp, B, self.nnz), "float32")}
        arrs["y"] = ((P, nbp, B), "float32")
        return arrs


def pad_examples(y: np.ndarray, multiple: int, *,
                 X: np.ndarray | None = None,
                 idx: np.ndarray | None = None,
                 val: np.ndarray | None = None):
    """Pad n up to `multiple` with inert examples (x=0, y=+1)."""
    n = y.shape[0]
    n_pad = _ceil_to(max(n, 1), multiple)
    if n_pad == n:
        return y, X, idx, val
    extra = n_pad - n
    y = np.concatenate([y, np.ones(extra, dtype=y.dtype)])
    if X is not None:
        X = np.concatenate(
            [X, np.zeros((X.shape[0], extra), dtype=X.dtype)], axis=1)
    if idx is not None:
        idx = np.concatenate(
            [idx, np.zeros((extra, idx.shape[1]), dtype=idx.dtype)])
        val = np.concatenate(
            [val, np.zeros((extra, val.shape[1]), dtype=val.dtype)])
    return y, X, idx, val


def build_cache(path, name: str, *, y, X=None, idx=None, val=None,
                d: int | None = None, kind: str | None = None,
                bucket: int = 16, pods: int = 1,
                pad_multiple: int | None = None,
                nnz_multiple: int | None = None,
                objective: str = "logistic") -> "TileCache":
    """Pack arrays into bucket tiles and write a cache directory.

    Dense input: ``X (d, n)``; sparse input: ``idx/val (n, nnz)`` plus
    ``d``.  ``pad_multiple`` defaults to ``pods * bucket`` — callers
    that know the training topology pass the stricter
    pods*lanes*lanes*chunks*bucket so every partition mode divides.
    ``nnz_multiple`` (sparse only) zero-pads the row width with inert
    idx=0/val=0 columns up to that multiple, so cached tiles land
    lane-aligned for the sparse Pallas kernel (which needs nnz % 8 == 0
    — DESIGN.md S11); padding columns never change margins or updates.
    """
    path = pathlib.Path(path)
    if kind is None:
        kind = "dense" if X is not None else "sparse"
    y = np.ascontiguousarray(np.asarray(y, np.float32))
    n_examples = y.shape[0]
    mult = pad_multiple or (pods * bucket)
    mult = _ceil_to(mult, pods * bucket)

    if kind == "dense":
        X = np.ascontiguousarray(np.asarray(X, np.float32))
        d = X.shape[0]
        y, X, _, _ = pad_examples(y, mult, X=X)
        n = y.shape[0]
        d_pad = _ceil_to(d, _SUBLANE)
        nb = n // bucket
        Xp = np.zeros((d_pad, n), dtype=np.float32)
        Xp[:d] = X
        # (d_pad, nb, B) -> bucket-major tiles (pods, nb_pod, d_pad, B)
        tiles = np.transpose(Xp.reshape(d_pad, nb, bucket), (1, 0, 2))
        arrays = {"X": np.ascontiguousarray(tiles).reshape(
            pods, nb // pods, d_pad, bucket)}
        nnz = 0
    else:
        idx = np.ascontiguousarray(np.asarray(idx, np.int32))
        val = np.ascontiguousarray(np.asarray(val, np.float32))
        if d is None:
            raise ValueError("sparse build_cache requires d")
        if nnz_multiple:
            pad_w = _ceil_to(max(idx.shape[1], 1), nnz_multiple) \
                - idx.shape[1]
            if pad_w:
                idx = np.pad(idx, ((0, 0), (0, pad_w)))
                val = np.pad(val, ((0, 0), (0, pad_w)))
        y, _, idx, val = pad_examples(y, mult, idx=idx, val=val)
        n = y.shape[0]
        nnz = idx.shape[1]
        nb = n // bucket
        arrays = {
            "idx": idx.reshape(pods, nb // pods, bucket, nnz),
            "val": val.reshape(pods, nb // pods, bucket, nnz)}
        d_pad = d
    arrays["y"] = y.reshape(pods, nb // pods, bucket)

    meta = CacheMeta(name=name, kind=kind, n=n, n_examples=n_examples,
                     d=d, d_pad=d_pad, bucket=bucket, pods=pods,
                     nnz=nnz, objective=objective)
    path.mkdir(parents=True, exist_ok=True)
    crcs = {}
    tile_crcs = []
    for aname, arr in arrays.items():
        arr = np.ascontiguousarray(arr)
        crcs[aname] = zlib.crc32(arr.tobytes())
        tile_crcs.append(_tile_crcs(arr, meta.n_buckets))
        arr.tofile(path / f"{aname}.bin")
    # Sidecar next (arrays in array_specs order), meta.json LAST and
    # ATOMICALLY: meta.json is the validity marker, so a build killed
    # at any earlier point leaves a directory open_cache rejects (no
    # meta, or a stale-version one) and registry.materialize rebuilds.
    np.concatenate(tile_crcs).tofile(path / _TILECRC_FILE)
    doc = dict(dataclasses.asdict(meta), crc32=crcs)
    tmp = path / ".meta.json.tmp"
    tmp.write_text(json.dumps(doc, sort_keys=True, indent=1) + "\n")
    os.replace(tmp, path / "meta.json")
    return open_cache(path)


def _tile_crcs(arr: np.ndarray, n_buckets: int) -> np.ndarray:
    """crc32 of each bucket tile's bytes, as little-endian uint32."""
    flat = np.ascontiguousarray(arr).reshape(n_buckets, -1)
    return np.array([zlib.crc32(row.tobytes()) for row in flat],
                    dtype="<u4")


def _load_tilecrc(path: pathlib.Path,
                  meta: CacheMeta) -> dict[str, np.ndarray] | None:
    """Read the per-tile crc sidecar back into {array: (n_buckets,)}."""
    f = path / _TILECRC_FILE
    specs = meta.array_specs()
    want = meta.n_buckets * len(specs)
    if not f.exists() or f.stat().st_size != want * 4:
        return None
    raw = np.fromfile(f, dtype="<u4", count=want)
    return {aname: raw[i * meta.n_buckets:(i + 1) * meta.n_buckets]
            for i, aname in enumerate(specs)}


def open_cache(path, *, verify: bool = False) -> "TileCache":
    """mmap an existing cache directory; validates magic/version/sizes."""
    path = pathlib.Path(path)
    doc = json.loads((path / "meta.json").read_text())
    if doc.get("magic") != CACHE_MAGIC:
        raise ValueError(f"{path}: not a {CACHE_MAGIC} directory")
    if doc.get("version") != CACHE_VERSION:
        raise ValueError(f"{path}: cache version {doc.get('version')} != "
                         f"supported {CACHE_VERSION}; rebuild the cache")
    crcs = doc.pop("crc32", {})
    meta = CacheMeta(**{f.name: doc[f.name]
                        for f in dataclasses.fields(CacheMeta)})
    tilecrc = _load_tilecrc(path, meta)
    arrays = {}
    for aname, (shape, dtype) in meta.array_specs().items():
        f = path / f"{aname}.bin"
        want = int(np.prod(shape)) * np.dtype(dtype).itemsize
        if f.stat().st_size != want:
            raise ValueError(
                f"{f}: {f.stat().st_size} bytes on disk, expected {want} "
                f"for shape {shape} — cache is truncated or corrupt")
        mm = np.memmap(f, dtype=dtype, mode="r", shape=shape)
        arrays[aname] = mm
    cache = TileCache(meta=meta, path=path, arrays=arrays, tilecrc=tilecrc)
    if verify:
        if tilecrc is not None:
            cache.verify_tiles()
        else:
            for aname, mm in arrays.items():
                if zlib.crc32(mm.tobytes()) != crcs.get(aname):
                    raise TileCorruptionError(path / f"{aname}.bin", aname)
    return cache


@dataclasses.dataclass
class TileCache:
    """An opened cache: meta + read-only memmaps of the tile arrays."""
    meta: CacheMeta
    path: pathlib.Path
    arrays: dict[str, np.memmap]
    tilecrc: dict[str, np.ndarray] | None = None

    def _flat(self, name: str) -> np.ndarray:
        """(pods, nb_pod, ...) view -> (n_buckets, ...) for id math."""
        a = self.arrays[name]
        return a.reshape((self.meta.n_buckets,) + a.shape[2:])

    def verify_tiles(self, bids: np.ndarray | None = None) -> None:
        """Check the crc32 of bucket tiles against the sidecar.

        ``bids`` is a set of GLOBAL bucket ids (any shape); None means
        every tile.  Raises `TileCorruptionError` pointing at the first
        bad tile.  Cost scales with the bytes actually checked, so a
        streamed feed can verify only the tiles a chunk touches.
        """
        if self.tilecrc is None:
            raise ValueError(
                f"{self.path}: no {_TILECRC_FILE} sidecar — rebuild the "
                f"cache to enable per-tile verification")
        ids = (np.arange(self.meta.n_buckets) if bids is None
               else np.unique(np.asarray(bids).reshape(-1)))
        for aname in self.meta.array_specs():
            flat = self._flat(aname)
            tile_nbytes = int(np.prod(flat.shape[1:])) * flat.dtype.itemsize
            want = self.tilecrc[aname]
            for b in ids:
                b = int(b)
                if zlib.crc32(np.ascontiguousarray(
                        flat[b]).tobytes()) != int(want[b]):
                    raise TileCorruptionError(
                        self.path / f"{aname}.bin", aname, tile=b,
                        offset=b * tile_nbytes)

    # -- bulk load (the in-memory path) ----------------------------------
    def load_arrays(self):
        """Unpack tiles to flat example order, fully in memory.

        Dense: (X (d, n), y).  Sparse: ((idx, val), y).  Exactly the
        arrays `build_cache` packed (padding included), so in-memory
        and streamed training see identical data.
        """
        m = self.meta
        y = np.ascontiguousarray(self._flat("y")).reshape(m.n)
        if m.kind == "dense":
            t = np.ascontiguousarray(self._flat("X"))  # (nb, d_pad, B)
            X = np.transpose(t, (1, 0, 2)).reshape(m.d_pad, m.n)[:m.d]
            return np.ascontiguousarray(X), y
        idx = np.ascontiguousarray(self._flat("idx")).reshape(m.n, m.nnz)
        val = np.ascontiguousarray(self._flat("val")).reshape(m.n, m.nnz)
        return (idx, val), y

    # -- tile gather (the out-of-core path) ------------------------------
    def gather_buckets(self, bids: np.ndarray):
        """Gather whole bucket tiles by GLOBAL bucket id.

        bids (*lead, nb) int -> dense  (data (*lead, d, nb*B), y ...)
                              -> sparse ((idx, val) (*lead, nb*B, nnz), y)
        Only the touched tiles are read from the mmap.
        """
        m = self.meta
        bids = np.asarray(bids)
        lead, nb = bids.shape[:-1], bids.shape[-1]
        y = self._flat("y")[bids].reshape(lead + (nb * m.bucket,))
        if m.kind == "dense":
            t = self._flat("X")[bids]          # (*lead, nb, d_pad, B)
            t = np.swapaxes(t, -3, -2).reshape(
                lead + (m.d_pad, nb * m.bucket))
            return t[..., :m.d, :], y
        idx = self._flat("idx")[bids].reshape(
            lead + (nb * m.bucket, m.nnz))
        val = self._flat("val")[bids].reshape(
            lead + (nb * m.bucket, m.nnz))
        return (idx, val), y

    def slice_gather(self, bids: np.ndarray, lo: int, hi: int, *,
                     nnz_multiple: int = 8, positions: bool = False,
                     width: int | None = None, gathered=None):
        """Gather sparse bucket tiles compacted to a feature slice [lo, hi).

        Building block for streamed feature-sharded feeds (DESIGN.md
        S12/S16): a model-axis lane that owns rows [lo, hi) of the
        shared vector only needs the nonzeros landing in its slice.
        Compaction semantics (row-order preserved, padded to a common
        width) live in `compact_slice_rows` — ``positions``/``width``
        pass through: the default mode returns slice-LOCAL
        ``((idx_loc, val_loc), y)``, while ``positions=True`` returns
        the mesh transfer format ``((idx, val, pos), y)`` with global
        ids + original within-row positions, which
        `engine.MeshChunkFeed` ships per model lane and the mesh step
        scatters back into exact full rows (the per-lane
        slice-compacted feed — ~M-fold fewer per-lane H2D bytes).

        ``gathered`` short-circuits the tile read with the result of a
        prior ``gather_buckets(bids)`` call, so a feed compacting the
        same chunk for M lanes reads the mmap once.
        """
        m = self.meta
        if m.kind != "sparse":
            raise ValueError("slice_gather is sparse-only")
        (idx, val), y = (gathered if gathered is not None
                         else self.gather_buckets(bids))
        out = compact_slice_rows(idx, val, lo, hi,
                                 nnz_multiple=nnz_multiple,
                                 positions=positions, width=width)
        return out, y

    def feed(self, *, verify: bool = False) -> "TileFeed":
        return TileFeed(self, verify=verify)


# ---------------------------------------------------------------------------
# ChunkFeed implementations (the protocol lives in core.engine)
# ---------------------------------------------------------------------------


class TileFeed:
    """`ChunkFeed` over a `TileCache`: mmap gather + device put.

    ``verify=True`` crc-checks exactly the tiles each fetch touches
    against the per-tile sidecar before handing them to the engine
    (raising `TileCorruptionError` so `ResilientChunkFeed` can
    quarantine + rebuild).  Default off: the fault-free hot loop pays
    zero checksum cost.
    """

    def __init__(self, cache: TileCache, *, verify: bool = False):
        self.cache = cache
        self.verify = verify
        m = cache.meta
        self.n, self.d, self.bucket = m.n, m.d, m.bucket
        self.sparse = m.kind == "sparse"

    def fetch(self, bids: np.ndarray):
        import jax

        from repro import obs
        if self.verify:
            self.cache.verify_tiles(bids)
        data, y = self.cache.gather_buckets(bids)
        if self.sparse:
            idx, val = data
            obs.add("h2d_bytes", idx.nbytes + val.nbytes + y.nbytes)
            return ((jax.device_put(idx), jax.device_put(val)),
                    jax.device_put(y))
        data = np.ascontiguousarray(data)
        obs.add("h2d_bytes", data.nbytes + y.nbytes)
        return jax.device_put(data), jax.device_put(y)


class ArrayFeed:
    """`ChunkFeed` over resident host arrays — the in-memory twin of
    `TileFeed`, used by tests to separate cache exactness from the
    streamed-loop contract."""

    def __init__(self, y, *, X=None, idx=None, val=None,
                 d: int | None = None, bucket: int = 16):
        self.y = np.asarray(y, np.float32)
        self.n, self.bucket = self.y.shape[0], bucket
        self.sparse = X is None
        if self.sparse:
            self.idx = np.asarray(idx, np.int32)
            self.val = np.asarray(val, np.float32)
            self.d = int(d)
        else:
            self.X = np.asarray(X, np.float32)
            self.d = self.X.shape[0]

    def _cols(self, bids: np.ndarray) -> np.ndarray:
        B = self.bucket
        return (bids[..., None] * B
                + np.arange(B, dtype=np.int32)).reshape(
                    bids.shape[:-1] + (-1,))

    def fetch(self, bids: np.ndarray):
        import jax

        from repro import obs
        cols = self._cols(np.asarray(bids))
        y = self.y[cols]
        if self.sparse:
            idx, val = self.idx[cols], self.val[cols]
            obs.add("h2d_bytes", idx.nbytes + val.nbytes + y.nbytes)
            return ((jax.device_put(idx), jax.device_put(val)),
                    jax.device_put(y))
        data = np.ascontiguousarray(
            np.moveaxis(self.X[:, cols], 0, -2))   # (*lead, d, m)
        obs.add("h2d_bytes", data.nbytes + y.nbytes)
        return jax.device_put(data), jax.device_put(y)
