"""Pallas TPU kernels: one worker's SPARSE bucketed SDCA sub-epoch.

The sparse twin of kernels/sdca_bucket.py (DESIGN.md S11/S12).  The XLA
formulation (`core.sdca.sparse_local_subepoch`) is a per-coordinate
`lax.scan` whose carry is the FULL shared vector v: every coordinate
pays a v-sized gather + scatter through HBM.  Here the paper's
cache-resident shared vector maps onto VMEM:

  * v is pinned in VMEM for the whole sub-epoch, laid out LANE-DENSE as
    (d_pad / 128, 128) f32 — feature p lives at row p >> 7, lane
    p & 127, so d features take d_pad * 4 bytes of VMEM (a (d, 1)
    column would be padded to 128 lanes by the (8, 128) tiling).  It is
    copied in from HBM once at the first bucket and written back once
    after the last; idx/val tiles are the ONLY per-bucket HBM traffic;
  * each grid step streams one (B, nnz) idx/val tile pair HBM->VMEM —
    exactly the mmap-aligned layout `data/cache.py` stores, so cached
    tiles DMA straight in.  The idx tile also lands in SMEM: feature
    ids are scalar addresses, and only SMEM feeds the scalar unit;
  * the touched feature rows are gathered once per bucket into a
    bucket-local working set W (B, nnz) (VMEM scratch) at bucket entry;
  * the in-bucket recursion runs on VMEM-resident state only: per
    coordinate one margin, one scalar dual update, one update row u,
    and an nnz-step pass that adds u's entries into every working-set
    entry that aliases the same feature (a (B, nnz) compare/add/select
    per step on W held in registers — later rows see the v the scan
    would show them);
  * after the recursion W[i, k] IS the bucket's final v at feature
    idx[i, k], so v is written back once per bucket by plain stores of
    W's entries (no load, no add, no order) instead of once per
    coordinate.

The three nnz loops (gather, alias, write-back) run as straight-line
code up to `UNROLL_NNZ` entries a row and as rolled loops of
`ENTRY_BLOCK` unrolled entries above it (`entry_loop_form`): a rolled
loop of one entry a step waits on each step's cross-lane reduction, so
the entries must be independent instructions the scheduler can overlap.

Mosaic has no dynamic slicing of VALUES, so every per-coordinate read
goes through a ref: rows via `pl.ds`, scalars from SMEM, a lane of a
row by an iota-mask select (`_lane`), and writes into a row by a
lane-masked store (`_put_v`).

Bit-equivalence contract: for the same visiting order the kernel is
BITWISE-identical to `sparse_local_subepoch` (pinned by interpret-mode
tests on CPU).  Two things make that hold and must not be "simplified"
away:

  * every floating-point add applies the exact values the scan adds,
    in the scan's order — the per-coordinate update row
    u = (sigma' * delta / lam_n) * val is computed ONCE (same
    association as the scan) and only ever ADDED elementwise; folding
    the multiply into the adds lets XLA fuse them into FMAs and drifts
    low bits;
  * rows must satisfy the CSR invariant: no duplicate feature id with
    a nonzero value within a row (padding with idx=0/val=0 is fine —
    zero-valued duplicates add exact zeros on both paths).  Real
    svmlight/CSR data satisfies this by construction;
    `data/formats.zero_duplicates` enforces it for synthetic data.

The write-back stores W's bits where the scan adds into v: a touched
feature's W entries start from v's value there and receive every add
the scan makes to it, in its order, so they hold the scan's final v.
Only a zero's sign may differ (`_lane` reads -0.0 as +0.0 and never
yields -0.0 for an add), and v_final - v0 erases it.

Grid is 1-D over buckets with "arbitrary" dimension semantics: buckets
are processed IN ORDER (sequential SDCA semantics).

Alignment: B and nnz must be multiples of 8 (f32 sublane tile); v is
padded to a multiple of `V_ALIGN` features and must fit the VMEM budget
below.  Scalars (lam*n, sigma') ride in SMEM.

Feature-sharded variant (DESIGN.md S12): when v cannot fit one core's
VMEM budget, each `model`-axis lane owns ONE contiguous
d_loc = roundup(ceil(d / M), V_ALIGN) slice of v instead.  The
sub-epoch becomes a per-bucket pair of kernels around one model-axis
exchange:

  * `_gather_slice_kernel`: gather the bucket's touched rows that fall
    in this lane's slice (out-of-slice entries read as exact 0.0);
  * the ENGINE all-gathers the per-lane partial working sets and each
    lane keeps, entry for entry, the owning lane's bits
    (`ops.sdca_sparse_sharded_subepoch`) — pure data movement, so the
    assembled W is bitwise the replicated kernel's W.  A psum of
    per-lane partial margins would be cheaper on the wire but changes
    the summation order and breaks the bitwise-vs-scan contract;
  * `_sharded_kernel`: run the SAME in-bucket recursion
    (`_bucket_recursion`, shared code) on the assembled W — every lane
    redundantly — then write back only the owned entries into the
    slice.

One exchange (M*B*nnz f32) per bucket is the whole model-axis wire
cost, amortized over B coordinates — the bucket optimization's payoff
on this axis too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.objectives import Objective

Array = jax.Array

#: Lanes per v row: feature p lives at (p >> 7, p & 127).
LANES = 128

#: v is padded to a multiple of this many features, so its
#: (d_pad / 128, 128) layout fills whole (8, 128) f32 tiles.
V_ALIGN = 8 * LANES

#: VMEM bytes the resident shared vector may occupy.  A v5e TensorCore
#: has 128 MiB of VMEM, but Mosaic grants a kernel a 16 MiB scoped
#: window by default; v gets half of it, leaving room for the
#: double-buffered idx/val tiles and the working set.  d above this
#: must use local_solver="xla" (HBM-resident v) or shard features.
V_VMEM_BUDGET_BYTES = 8 * 2 ** 20

#: Total VMEM the kernel's buffers may claim together: the default
#: 16 MiB scoped window less headroom for Mosaic's own scratch.
#: Exceeding the window inside Mosaic is a compile-time OOM, not a
#: Python error, so the footprint is budgeted up front.
TOTAL_VMEM_BUDGET_BYTES = 14 * 2 ** 20


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def v_pad(d: int) -> int:
    """Features the lane-dense v of d features is padded to."""
    return _round_up(max(int(d), 1), V_ALIGN)


def v_bytes(d: int) -> int:
    """VMEM bytes of a resident lane-dense v (or v slice) of d features."""
    return v_pad(d) * 4


def _tile_bytes(rows: int, cols: int) -> int:
    """VMEM bytes of one (rows, cols) 32-bit buffer in (8, 128) tiles."""
    return _round_up(max(rows, 1), 8) * _round_up(max(cols, 1), LANES) * 4


def vmem_bytes_estimate(B: int, nnz: int, d: int) -> int:
    """VMEM footprint of the replicated kernel, as the compiler lays it
    out (pinned against a v5e compile in tests/test_tpu_compile.py).

    Counts the one resident copy of v, the double-buffered idx(int32)
    and val(f32) tiles, the double-buffered (1, B) y/alpha/q/alpha-out
    rows, and the W working-set scratch.  Shared with
    `ops.sparse_solver_plan` so the "auto" path can pre-check static
    shapes and fall back instead of raising.
    """
    tiles = 2 * 2 * _tile_bytes(B, nnz)
    rows = 4 * 2 * _tile_bytes(1, B)
    work = _tile_bytes(B, nnz)
    return v_bytes(d) + tiles + rows + work


def vmem_bytes_estimate_sharded(B: int, nnz: int, d_loc: int) -> int:
    """VMEM footprint of ONE bucket of the sharded pair.

    The update kernel dominates: the resident v SLICE, one (not
    double-buffered — one bucket per call) idx/val tile pair, the
    exchanged working set W, the y/alpha/q/alpha-out rows and the W
    scratch the recursion updates.  Shared with
    `ops.sparse_solver_plan` so the dispatcher can pre-check the
    sharded route on static shapes.
    """
    tiles = 2 * _tile_bytes(B, nnz)
    wexch = _tile_bytes(B, nnz)
    rows = 4 * _tile_bytes(1, B)
    work = _tile_bytes(B, nnz)
    return v_bytes(d_loc) + tiles + wexch + rows + work


def _lane(row, k):
    """row[0, k] of a (1, n) value as a (1, 1) value: an iota-mask
    select summed over lanes (exactly row[0, k] — every other term is
    +0.0)."""
    ids = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(ids == k, row, 0.0), axis=1, keepdims=True)


def _read_v(v_ref, p, lane_ok=True):
    """v[p] of a lane-dense (rows, 128) v ref as a (1, 1) value, or
    exact 0.0 where `lane_ok` is False."""
    row = v_ref[pl.ds(p >> 7, 1), :]
    return _lane(row, jnp.where(lane_ok, p & (LANES - 1), -1))


def _put_v(v_ref, p, x, lane_ok=True):
    """v[p] = x (x a (1, 1) value) by a lane-masked store: no load, no
    add; nothing is stored where `lane_ok` is False."""
    ids = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    hit = ids == jnp.where(lane_ok, p & (LANES - 1), -1)
    pltpu.store(v_ref.at[pl.ds(p >> 7, 1), :],
                jnp.broadcast_to(x, (1, LANES)), mask=hit)


#: Rows up to this many entries run their nnz loops fully unrolled;
#: wider rows run a rolled loop over blocks of `ENTRY_BLOCK` unrolled
#: entries, which bounds the code size (webspam's 3,728-wide rows).
UNROLL_NNZ = 128

#: Entries a block of the rolled form; nnz % 8 == 0 is the tile contract.
ENTRY_BLOCK = 8


def entry_loop_form(nnz: int) -> tuple[int, int]:
    """(blocks, entries a block) of the nnz loops for a static nnz:
    (1, nnz), one straight-line block, up to `UNROLL_NNZ`, else
    (nnz / ENTRY_BLOCK, ENTRY_BLOCK)."""
    if nnz <= UNROLL_NNZ:
        return 1, nnz
    return nnz // ENTRY_BLOCK, ENTRY_BLOCK


def _for_entries(nnz: int, body, carry):
    """carry = body(k, carry) for k = 0 .. nnz-1, in order, in the form
    `entry_loop_form` picks.  Unrolled entries are independent
    instructions the scheduler can overlap wherever `body` carries no
    dependence through `carry`.  `fori_loop(..., unroll=True)` traces
    `body` once and unrolls it in the lowering, so a straight-line row
    costs no more Python tracing than a rolled one."""
    blocks, width = entry_loop_form(nnz)
    if blocks == 1:
        return jax.lax.fori_loop(0, nnz, body, carry, unroll=True)

    def block(b, c):
        return jax.lax.fori_loop(
            0, width, lambda j, cj: body(b * width + j, cj), c, unroll=True)

    return jax.lax.fori_loop(0, blocks, block, carry)


def _gather_rows(idx_s, w_ref, read):
    """w_ref[i, k] = read(idx[i, k]) for the whole (B, nnz) tile.

    Shared by the replicated kernel (read = v lookup) and the sharded
    gather kernel (read = masked slice lookup): the loop structure must
    stay identical so both produce the same W bits for owned entries.
    A row's nnz reads are independent; only the select into the row
    chains them.
    """
    B, nnz = w_ref.shape
    ids = jax.lax.broadcasted_iota(jnp.int32, (1, nnz), 1)

    def row(i, carry):
        def entry(k, w):
            return jnp.where(ids == k, read(idx_s[i, k]), w)

        w_ref[pl.ds(i, 1), :] = _for_entries(
            nnz, entry, jnp.zeros((1, nnz), jnp.float32))
        return carry

    jax.lax.fori_loop(0, B, row, 0)


def _bucket_recursion(obj: Objective, idx_s, idx, val_ref, y, a0, qrow,
                      lam_n, sig, w_ref):
    """The in-bucket delta recursion on the gathered working set in
    `w_ref`; returns the (1, B) alpha deltas and leaves in `w_ref` the
    bucket's final v at every entry's feature.

    Shared VERBATIM by the replicated and sharded kernels — the sharded
    path's bitwise claim is exactly "same W bits in, same W bits out".
    After coordinate i, every working-set entry that aliases a feature
    i touched receives the SAME u-element the scan scatter-adds into v,
    in the scan's order, so later margins stay bit-equal.  W rides in
    registers through a row's alias pass (one load, one store); the
    lane extractions of u do not depend on W, so they overlap and only
    the compare/add/select on W is serial.
    """
    B, nnz = w_ref.shape
    lanes_b = jax.lax.broadcasted_iota(jnp.int32, (1, B), 1)

    def body(i, deltas):
        vi = val_ref[pl.ds(i, 1), :].astype(jnp.float32)      # (1, nnz)
        m = jnp.sum(w_ref[pl.ds(i, 1), :] * vi, axis=1, keepdims=True)
        d = obj.delta(m, _lane(a0, i), _lane(y, i),
                      sig * _lane(qrow, i) / lam_n)
        # the scan's update row, computed once with its association
        u = (sig * d / lam_n) * vi

        def alias(k, W):
            return jnp.where(idx == idx_s[i, k], W + _lane(u, k), W)

        w_ref[...] = _for_entries(nnz, alias, w_ref[...])
        return jnp.where(lanes_b == i, d, deltas)

    return jax.lax.fori_loop(0, B, body, jnp.zeros((1, B), jnp.float32))


def _write_back(idx_s, w_ref, put):
    """put(idx[i, k], W[i, k]) over the tile.

    After the recursion W[i, k] holds, bit for bit, the final v at
    feature idx[i, k]: the gather read v there and the alias passes
    added every u-element the scan adds to it, in the scan's order.
    Entries that alias one feature hold identical bits, so the puts
    need no add and no order.
    """
    B, nnz = w_ref.shape

    def row(i, carry):
        w = w_ref[pl.ds(i, 1), :]

        def entry(k, c):
            put(idx_s[i, k], _lane(w, k))
            return c

        return _for_entries(nnz, entry, carry)

    jax.lax.fori_loop(0, B, row, 0)


def _kernel(obj: Objective, idx_s, idx_ref, val_ref, y_ref, a_ref, q_ref,
            scal_ref, v_hbm, aout_ref, v_ref, w_ref):
    """Body for one bucket (one grid step) — replicated v."""
    # v lives in the aliased output block; copy it in from HBM once
    @pl.when(pl.program_id(0) == 0)
    def _():
        pltpu.sync_copy(v_hbm, v_ref)

    # per-row curvature ||x_i||^2 arrives PRECOMPUTED by the wrapper
    # with the scan's own `sdca.row_sq_norms`: recomputing it per
    # tile inside the kernel lets XLA vectorize the reduction
    # differently and drifts q by 1 ulp on some rows, which the
    # Newton update carries into d — the bitwise contract dies there.
    a0 = a_ref[...].astype(jnp.float32)                     # (1, B)
    lam_n = scal_ref[0]
    sig = scal_ref[1]

    # 1. bucket entry: gather the touched rows into the working set
    #    W[i, k] = v[idx[i, k]]  (the only reads of v this bucket)
    _gather_rows(idx_s, w_ref, lambda p: _read_v(v_ref, p))

    # 2. in-bucket recursion entirely on VMEM-resident state
    deltas = _bucket_recursion(
        obj, idx_s, idx_ref[...], val_ref, y_ref[...].astype(jnp.float32),
        a0, q_ref[...].astype(jnp.float32), lam_n, sig, w_ref)

    # 3. write W back into v ONCE per bucket
    _write_back(idx_s, w_ref, lambda p, x: _put_v(v_ref, p, x))
    aout_ref[...] = (a0 + deltas).astype(aout_ref.dtype)


def _row_spec(B: int) -> pl.BlockSpec:
    """(nb, 1, B) per-bucket vectors, one (1, B) row per grid step."""
    return pl.BlockSpec((None, 1, B), lambda i: (i, 0, 0))


@functools.partial(jax.jit, static_argnums=(0, 8, 9))
def sdca_sparse_bucket_kernel(obj: Objective, idx: Array, val: Array,
                              yb: Array, ab: Array, qb: Array,
                              v0: Array, scal: Array,
                              interpret: bool = False,
                              source: str = "ad-hoc arrays"
                              ) -> tuple[Array, Array]:
    """Run the sparse sub-epoch kernel.

    idx/val: (nb, B, nnz) bucket tiles in visiting order (the tile
    cache's on-disk layout); yb, ab, qb: (nb, B) — qb is the per-row
    curvature sum(val^2) precomputed at full-chunk shape (see _kernel);
    v0: (d_pad / 128, 128) f32 lane-dense v; scal: (2,) f32 =
    [lam*n, sigma'].  Returns (a_new (nb, B), v_final like v0); v_final
    includes the sigma'-scaled local evolution (callers unscale the
    global delta).  `source` names where the tiles came from so
    alignment errors point at the right fix.
    """
    nb, B, nnz = idx.shape
    d_pad = v0.shape[0] * v0.shape[1]
    if B % 8 or nnz % 8:
        raise ValueError(
            f"sparse bucket tiles from {source} have (B={B}, nnz={nnz}); "
            f"the Pallas kernel needs both to be multiples of 8 "
            f"(f32 sublane tile).  Fix: rebuild the tile cache with "
            f"build_cache(..., nnz_multiple=8) / materialize(..., "
            f"nnz_multiple=8) for cached tiles, or zero-pad ad-hoc "
            f"idx/val arrays with idx=0/val=0 columns (and pick a "
            f"bucket size that is a multiple of 8).")
    if v0.shape[1] != LANES or d_pad % V_ALIGN:
        raise ValueError(
            f"v tile from {source} has shape {v0.shape}; the kernel "
            f"needs a lane-dense (rows, {LANES}) v with rows a multiple "
            f"of 8 — pad the shared vector with zero features "
            f"(ops.sdca_sparse_bucket_subepoch does this automatically)")
    if v_bytes(d_pad) > V_VMEM_BUDGET_BYTES:
        raise ValueError(
            f"shared vector of d_pad={d_pad} features ({v_bytes(d_pad)} "
            f"bytes) exceeds the sparse kernel's VMEM budget "
            f"({V_VMEM_BUDGET_BYTES} bytes, ~{V_VMEM_BUDGET_BYTES // 4} "
            f"features).  Use local_solver='xla' (HBM-resident v) for "
            f"this workload, or shard features.")
    need = vmem_bytes_estimate(B, nnz, d_pad)
    if need > TOTAL_VMEM_BUDGET_BYTES:
        raise ValueError(
            f"sparse bucket tiles from {source} with (B={B}, nnz={nnz}, "
            f"d_pad={d_pad}) need ~{need} bytes of VMEM (resident v + "
            f"double-buffered (B, nnz) tiles + working set), over the "
            f"kernel's {TOTAL_VMEM_BUDGET_BYTES}-byte total budget.  Use "
            f"local_solver='xla' (HBM-resident v) for this workload, or "
            f"shrink bucket/nnz so the tiles fit.")

    tile = pl.BlockSpec((None, B, nnz), lambda i: (i, 0, 0))
    a_new, v_fin = pl.pallas_call(
        functools.partial(_kernel, obj),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((None, B, nnz), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            tile, tile, _row_spec(B), _row_spec(B), _row_spec(B),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            _row_spec(B),
            pl.BlockSpec(v0.shape, lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, 1, B), ab.dtype),
            jax.ShapeDtypeStruct(v0.shape, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((B, nnz), jnp.float32)],
        input_output_aliases={7: 1},   # v0 buffer reused as v_final
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(idx, idx, val, yb[:, None], ab[:, None], qb[:, None], scal, v0)
    return a_new[:, 0], v_fin


# ---------------------------------------------------------------------------
# Feature-sharded (model-axis) variant: per-bucket kernel pair around one
# engine-side exchange (see module docstring + DESIGN.md S12).  Driven by
# ops.sdca_sparse_sharded_subepoch, which owns the bucket scan and the
# all-gather/owner-select exchange between the two calls.
# ---------------------------------------------------------------------------


def _owned(p, lo, d_loc):
    """(slice-local feature id, owned?) for global feature p."""
    q = p - lo
    ok = jnp.logical_and(q >= 0, q < d_loc)
    return jnp.where(ok, q, 0), ok


def _gather_slice_kernel(idx_s, lo_ref, v_ref, w_ref):
    """W_loc[i, k] = v_slice[idx[i, k] - lo] when owned, else exact 0.0.

    The masked read keeps the owned entries' bits identical to the
    replicated kernel's gather; unowned entries are filled by the
    owning lane after the exchange.
    """
    lo = lo_ref[0]
    d_loc = v_ref.shape[0] * v_ref.shape[1]
    _gather_rows(idx_s, w_ref,
                 lambda p: _read_v(v_ref, *_owned(p, lo, d_loc)))


def _sharded_kernel(obj: Objective, idx_s, idx_ref, val_ref, y_ref, a_ref,
                    q_ref, w_in_ref, scal_ref, lo_ref, v_hbm, aout_ref,
                    v_ref, w_ref):
    """One bucket's recursion + owned-slice write-back, given the
    EXCHANGED working set W (full bits on every lane)."""
    pltpu.sync_copy(v_hbm, v_ref)
    w_ref[...] = w_in_ref[...].astype(jnp.float32)
    a0 = a_ref[...].astype(jnp.float32)                     # (1, B)
    lo = lo_ref[0]
    d_loc = v_ref.shape[0] * v_ref.shape[1]

    # every lane runs the full recursion on the same W bits (redundant
    # O(B*nnz) VPU work — the price of one exchange per bucket)
    deltas = _bucket_recursion(
        obj, idx_s, idx_ref[...], val_ref, y_ref[...].astype(jnp.float32),
        a0, q_ref[...].astype(jnp.float32), scal_ref[0], scal_ref[1],
        w_ref)

    # write back the OWNED entries; an unowned entry stores nothing, so
    # each v entry gets on its one owner the replicated kernel's bits
    def put(p, x):
        q, ok = _owned(p, lo, d_loc)
        _put_v(v_ref, q, x, ok)

    _write_back(idx_s, w_ref, put)
    aout_ref[...] = (a0 + deltas).astype(aout_ref.dtype)


def _check_sharded_tile(B: int, nnz: int, v_loc_shape, source: str):
    if B % 8 or nnz % 8:
        raise ValueError(
            f"sparse bucket tiles from {source} have (B={B}, nnz={nnz}); "
            f"the sharded Pallas kernel needs both to be multiples of 8 "
            f"(f32 sublane tile) — rebuild the tile cache with "
            f"nnz_multiple=8 or zero-pad ad-hoc idx/val arrays.")
    rows, lanes = v_loc_shape
    if lanes != LANES or rows % 8:
        raise ValueError(
            f"v slice from {source} has shape {tuple(v_loc_shape)}; the "
            f"kernel needs a lane-dense (rows, {LANES}) slice with rows a "
            f"multiple of 8 (ops.sdca_sparse_sharded_subepoch sizes "
            f"slices to the tile automatically)")
    d_loc = rows * lanes
    if v_bytes(d_loc) > V_VMEM_BUDGET_BYTES:
        raise ValueError(
            f"per-lane v slice of d_loc={d_loc} features "
            f"({v_bytes(d_loc)} bytes) exceeds the sparse kernel's VMEM "
            f"budget ({V_VMEM_BUDGET_BYTES} bytes) even feature-sharded.  "
            f"Add model-axis lanes or use local_solver='xla' "
            f"(HBM-resident v).")
    need = vmem_bytes_estimate_sharded(B, nnz, d_loc)
    if need > TOTAL_VMEM_BUDGET_BYTES:
        raise ValueError(
            f"sharded sparse bucket tiles from {source} with (B={B}, "
            f"nnz={nnz}, d_loc={d_loc}) need ~{need} bytes of VMEM "
            f"(resident v slice + (B, nnz) tiles + working sets), over "
            f"the kernel's {TOTAL_VMEM_BUDGET_BYTES}-byte total budget.  "
            f"Use local_solver='xla' for this workload, or shrink "
            f"bucket/nnz so the tiles fit.")


@functools.partial(jax.jit, static_argnums=(3, 4))
def sdca_sparse_gather_bucket(idx_t: Array, v_loc: Array, lo: Array,
                              interpret: bool = False,
                              source: str = "ad-hoc arrays") -> Array:
    """Gather ONE bucket's per-lane partial working set.

    idx_t: (B, nnz) int32 feature ids; v_loc: (d_loc / 128, 128) f32
    this lane's lane-dense v slice; lo: () int32 the slice's first
    global feature.  Returns W_loc (B, nnz) f32 with this lane's
    entries and exact zeros elsewhere.
    """
    B, nnz = idx_t.shape
    _check_sharded_tile(B, nnz, v_loc.shape, source)
    return pl.pallas_call(
        _gather_slice_kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, nnz), jnp.float32),
        interpret=interpret,
    )(idx_t, lo.astype(jnp.int32).reshape(1), v_loc)


@functools.partial(jax.jit, static_argnums=(0, 10, 11))
def sdca_sparse_sharded_bucket(obj: Objective, idx_t: Array, val_t: Array,
                               y_t: Array, a_t: Array, q_t: Array,
                               W: Array, v_loc: Array, scal: Array,
                               lo: Array, interpret: bool = False,
                               source: str = "ad-hoc arrays"
                               ) -> tuple[Array, Array]:
    """Run ONE bucket's recursion + owned write-back on the v slice.

    idx_t/val_t: (B, nnz); y_t/a_t/q_t: (B,); W: (B, nnz) the EXCHANGED
    full working set (every lane the same bits); v_loc: (d_loc / 128,
    128) this lane's lane-dense slice (aliased into the output); scal:
    (2,) [lam*n, sigma']; lo: () int32.  Returns (a_new (B,),
    v_loc_new like v_loc).
    """
    B, nnz = idx_t.shape
    _check_sharded_tile(B, nnz, v_loc.shape, source)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    a_new, v_fin = pl.pallas_call(
        functools.partial(_sharded_kernel, obj),
        in_specs=[smem, vmem, vmem, vmem, vmem, vmem, vmem, smem, smem,
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[vmem, vmem],
        out_shape=[
            jax.ShapeDtypeStruct((1, B), a_t.dtype),
            jax.ShapeDtypeStruct(v_loc.shape, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((B, nnz), jnp.float32)],
        input_output_aliases={9: 1},   # v slice reused as output
        interpret=interpret,
    )(idx_t, idx_t, val_t, y_t[None], a_t[None], q_t[None], W, scal,
      lo.astype(jnp.int32).reshape(1), v_loc)
    return a_new[0], v_fin
