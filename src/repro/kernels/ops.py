"""Jit'd wrappers around the Pallas kernels, with padding + CPU fallback.

`sdca_bucket_subepoch` is call-compatible with
`repro.core.sdca.dense_local_subepoch` so the epoch drivers can route
through the kernel with cfg.use_kernel=True.  The kernels' contracts —
`sparse_solver_plan`, `sparse_kernel_misfit` and `dense_kernel_misfit`
— live here; `core.planner.resolve_plan`, the one function that
chooses the local solver's route, calls them.
"""
from __future__ import annotations


import weakref

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sdca
from repro.core.objectives import Objective
from . import sdca_bucket, sdca_sparse_bucket, rglru as _rglru


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


class MisfitCode:
    """Stable enum-style codes for kernel misfit reasons.

    The human-readable reason strings below are free to change
    wording; tools (the planner's `SolverPlan.reason_code`, BENCH json
    consumers, the static auditor's report) key on these instead.
    """
    BUCKET_INDIVISIBLE = "BUCKET_INDIVISIBLE"   # B does not divide n_local
    ALIGNMENT = "ALIGNMENT"                     # B/nnz off the sublane tile
    BUCKET_CAP = "BUCKET_CAP"                   # dense recursion cap B<=512
    VMEM_V = "VMEM_V"                           # resident v over budget
    VMEM_TOTAL = "VMEM_TOTAL"                   # total footprint over budget


class Misfit(str):
    """A misfit reason string carrying its stable `MisfitCode`.

    Subclasses ``str`` so every existing consumer (equality and
    substring assertions, `SolverPlan.reason`, log lines) sees the
    plain reason text; code-aware consumers read ``.code``.
    """
    __slots__ = ("code",)
    code: str

    def __new__(cls, code: str, text: str) -> "Misfit":
        self = super().__new__(cls, text)
        self.code = code
        return self


def sparse_slice_width(d: int, model_lanes: int) -> int:
    """Per-lane slice width d_loc of the feature-sharded sparse kernel.

    The ONE formula shared by the kernel driver
    (`sdca_sparse_sharded_subepoch`), the masked XLA twin
    (`engine.sparse_sharded_xla_solver`), and the analytic cost models:
    ceil(d / M) rounded up to `sdca_sparse_bucket.V_ALIGN` features, so
    each lane-dense slice fills whole (8, 128) f32 tiles.  Slices are
    contiguous, disjoint, and cover [0, d) because d_loc * M >= d.
    """
    M = max(int(model_lanes), 1)
    return sdca_sparse_bucket.v_pad(-(-max(int(d), 1) // M))


def sparse_solver_plan(n_local: int, nnz: int, d: int, bucket: int, *,
                       model_lanes: int = 1) -> tuple[str, str | None]:
    """Data-parallel vs feature-parallel selection on static shapes.

    -> (route, reason): route is one of "pallas-replicated" (whole v in
    VMEM — the PR-4 kernel), "pallas-sharded" (each of `model_lanes`
    lanes owns a d/M slice of v), or "xla" (HBM-resident v scan), with
    `reason` the misfit string for "xla" routes and None otherwise.
    Prefers replicated (no per-bucket exchange) when v fits, mirroring
    LightGBM's data-parallel vs feature-parallel decision by
    #feature/#data shape (SNIPPETS.md Snippet 3) with VMEM budgets as
    the thresholds.  Mirrors the wrapper/kernel guards (bucket
    divisibility, B/nnz sublane alignment, VMEM budgets) so
    `core.planner.resolve_plan` can route misfits to the XLA scan
    instead of raising.
    """
    if bucket <= 0 or n_local % bucket:
        return "xla", Misfit(
            MisfitCode.BUCKET_INDIVISIBLE,
            f"bucket={bucket} does not divide n_local={n_local}")
    if bucket % 8 or nnz % 8:
        return "xla", Misfit(
            MisfitCode.ALIGNMENT,
            f"(B={bucket}, nnz={nnz}) must both be multiples of 8 "
            f"(f32 sublane tile)")
    ssb = sdca_sparse_bucket
    d_pad = ssb.v_pad(d)
    M = max(int(model_lanes), 1)
    if (ssb.v_bytes(d) <= ssb.V_VMEM_BUDGET_BYTES
            and ssb.vmem_bytes_estimate(bucket, nnz, d)
            <= ssb.TOTAL_VMEM_BUDGET_BYTES):
        return "pallas-replicated", None
    if M > 1:
        d_loc = sparse_slice_width(d, M)
        if (ssb.v_bytes(d_loc) <= ssb.V_VMEM_BUDGET_BYTES
                and ssb.vmem_bytes_estimate_sharded(bucket, nnz, d_loc)
                <= ssb.TOTAL_VMEM_BUDGET_BYTES):
            return "pallas-sharded", None
    if ssb.v_bytes(d) > ssb.V_VMEM_BUDGET_BYTES:
        text = (f"shared vector of d={d} features exceeds the "
                f"{sdca_sparse_bucket.V_VMEM_BUDGET_BYTES}-byte "
                f"resident-v VMEM budget")
        if M > 1:
            text += (f" (and its d/{M} model-axis slice does not fit "
                     f"the sharded kernel either)")
        reason = Misfit(MisfitCode.VMEM_V, text)
    else:
        need = sdca_sparse_bucket.vmem_bytes_estimate(bucket, nnz, d_pad)
        reason = Misfit(
            MisfitCode.VMEM_TOTAL,
            f"~{need}-byte VMEM footprint for (B={bucket}, "
            f"nnz={nnz}, d_pad={d_pad}) exceeds the "
            f"{sdca_sparse_bucket.TOTAL_VMEM_BUDGET_BYTES}-byte "
            f"total budget")
    return "xla", reason


def sparse_kernel_misfit(n_local: int, nnz: int, d: int, bucket: int,
                         model_lanes: int = 1) -> str | None:
    """Why NO sparse Pallas kernel can run this workload, or None.

    The boolean view of `sparse_solver_plan`: None when either the
    replicated or (given `model_lanes` > 1) the sharded kernel fits —
    replicated-feasible shapes are always sharded-feasible too, the
    slice and its single-buffered tiles never outgrow the replicated
    footprint — so callers on a feature-sharded layout can use it as a
    sharded-feasibility verdict directly.
    """
    route, reason = sparse_solver_plan(n_local, nnz, d, bucket,
                                       model_lanes=model_lanes)
    return reason if route == "xla" else None


def dense_kernel_misfit(d: int, n_local: int, bucket: int) -> str | None:
    """Why the dense Pallas kernel CANNOT run this workload, or None.

    The dense wrapper below zero-pads d and B to sublane multiples, so
    the only hard misfits are bucket divisibility, the kernel's B cap,
    and the VMEM footprint of the padded tiles.  Called by
    `core.planner.resolve_plan`, like `sparse_solver_plan`.
    """
    if bucket <= 0 or n_local % bucket:
        return Misfit(MisfitCode.BUCKET_INDIVISIBLE,
                      f"bucket={bucket} does not divide n_local={n_local}")
    B_pad = _round_up(max(bucket, 8), 8)
    if B_pad > sdca_bucket.MAX_BUCKET:
        return Misfit(MisfitCode.BUCKET_CAP,
                      f"bucket={bucket} exceeds the kernel's in-bucket "
                      f"recursion cap of B <= {sdca_bucket.MAX_BUCKET}")
    need = sdca_bucket.vmem_bytes_estimate(B_pad, d)
    if need > sdca_bucket.TOTAL_VMEM_BUDGET_BYTES:
        return Misfit(MisfitCode.VMEM_TOTAL,
                      f"~{need}-byte VMEM footprint for (B={B_pad}, "
                      f"d_pad={sdca_bucket.d_pad(d)}) exceeds the "
                      f"{sdca_bucket.TOTAL_VMEM_BUDGET_BYTES}-byte budget")
    return None


# weak-identity memo of (idx, val) pairs that already passed the
# CSR-invariant check, so eager epoch loops don't re-sort the same
# chunk every epoch (keyed on BOTH arrays: the invariant depends on
# the values, not just the ids)
_csr_checked: dict[tuple[int, int], tuple] = {}


def _csr_was_checked(idx, val) -> bool:
    entry = _csr_checked.get((id(idx), id(val)))
    return (entry is not None
            and entry[0]() is idx and entry[1]() is val)


def _csr_mark_checked(idx, val) -> None:
    # only immutable jax.Arrays are safe to memoize by identity —
    # a numpy array can be mutated in place after passing, which would
    # silently stale the memo and skip the check forever after
    if not (isinstance(idx, jax.Array) and isinstance(val, jax.Array)):
        return
    key = (id(idx), id(val))

    def _drop(_ref, _key=key):
        _csr_checked.pop(_key, None)
    try:
        _csr_checked[key] = (weakref.ref(idx, _drop),
                             weakref.ref(val, _drop))
    except TypeError:
        pass


#: provenances whose rows are vouched for upstream: cache builds run
#: `zero_duplicates`; array feeds are checked at Session entry
#: (api/session.py) or built from cached/registry data, and opaque
#: ChunkFeeds carry the invariant as part of the engine.ChunkFeed
#: protocol contract; resident shards only reach here as tracers.
#: Every OTHER label, including relabeled ad-hoc variants, gets
#: checked: the gate fails safe instead of keying on one magic string.
_TRUSTED_SOURCES = ("tile cache", "array feed", "resident shard arrays")


def _check_csr_invariant(idx, val, source: str) -> None:
    """Host-side check of the no-duplicate-nonzero CSR invariant.

    Runs on CONCRETE arrays from any untrusted provenance (tracers —
    i.e. calls from inside jitted epoch programs — are skipped; so are
    `_TRUSTED_SOURCES`, deduped upstream).  Violations silently break
    the bitwise-vs-XLA contract, so they get a loud error here.
    Arrays that pass are memoized by weak identity so eager training
    loops only pay the device-to-host copy + sort once per chunk, not
    once per epoch.
    """
    if any(source.startswith(s) for s in _TRUSTED_SOURCES):
        return
    if isinstance(idx, jax.core.Tracer) or isinstance(val, jax.core.Tracer):
        return
    if _csr_was_checked(idx, val):
        return
    from repro.data.formats import raise_on_duplicate_nonzeros
    raise_on_duplicate_nonzeros(np.asarray(idx), np.asarray(val),
                                f"{source}: sparse rows")
    _csr_mark_checked(idx, val)


def _lane_dense(v, d_pad: int):
    """(d,) v zero-padded to the sparse kernels' lane-dense
    (rows, 128) f32 layout of at least d_pad features."""
    d_pad = sdca_sparse_bucket.v_pad(d_pad)
    return jnp.zeros((d_pad,), jnp.float32).at[:v.shape[0]].set(
        v.astype(jnp.float32)).reshape(-1, sdca_sparse_bucket.LANES)


def sdca_bucket_subepoch(obj: Objective, Xl, yl, al, v0, lam_n, sig, *,
                         bucket: int, interpret: bool | None = None,
                         source: str = "ad-hoc arrays"):
    """One worker's sub-epoch via the Pallas kernel.

    Xl: (d, n_local) columns in visiting order; returns (a_new, dv_raw)
    where dv_raw is the UNSCALED global delta (CoCoA+ convention, same as
    dense_local_subepoch).  `source` labels the data's provenance
    (tile cache vs ad-hoc arrays) in alignment errors.
    """
    if interpret is None:
        interpret = _interpret_default()
    d, n_local = Xl.shape
    B = bucket
    nb = n_local // B
    d_pad = sdca_bucket.d_pad(d)
    B_pad = _round_up(max(B, 8), 8)

    xb = Xl.reshape(d, nb, B).transpose(1, 2, 0)      # (nb, B, d)
    if d_pad != d or B_pad != B:
        xb = jnp.pad(xb, ((0, 0), (0, B_pad - B), (0, d_pad - d)))
    yb = yl.reshape(nb, B)
    ab = al.reshape(nb, B)
    if B_pad != B:
        # padded coordinates: x column is all-zero => q=0, m=0.  Give them
        # y such that delta(0, 0, y, 0) == 0 for every objective:
        # ridge: (y-0-0)/(1+0) = y -> needs y=0;  hinge/logistic are safe
        # with y=+1 & a=0?  hinge: clip(0*1 + (1-0)/max(q,eps)) -> huge.
        # Zero columns make the v-update a no-op regardless of delta, and
        # alpha updates on padding are discarded, so any finite y works;
        # use y=0 for ridge-neutrality and rely on eps-guards elsewhere.
        yb = jnp.pad(yb, ((0, 0), (0, B_pad - B)))
        ab = jnp.pad(ab, ((0, 0), (0, B_pad - B)))

    v0p = jnp.zeros((1, d_pad), jnp.float32).at[0, :d].set(
        v0.astype(jnp.float32))
    scal = jnp.stack([jnp.float32(lam_n), jnp.float32(sig)])

    a_new, v_fin = sdca_bucket.sdca_bucket_kernel(
        obj, xb, yb, ab, v0p, scal, interpret, source)

    a_out = a_new[:, :B].reshape(-1)
    dv = (v_fin[0, :d] - v0.astype(jnp.float32)) / jnp.float32(sig)
    return a_out.astype(al.dtype), dv.astype(v0.dtype)


def sdca_sparse_bucket_subepoch(obj: Objective, idx, val, yl, al, v0,
                                lam_n, sig, *, bucket: int,
                                interpret: bool | None = None,
                                source: str = "ad-hoc arrays"):
    """One worker's SPARSE sub-epoch via the Pallas kernel.

    idx/val: (n_local, nnz) padded-CSR rows in visiting order; v0: (d,)
    replicated shared vector.  Returns (a_new, dv_raw) with dv_raw the
    UNSCALED global delta — call-compatible with
    `core.sdca.sparse_local_subepoch` and BITWISE-identical to it for
    rows obeying the CSR no-duplicate-nonzero invariant (see
    kernels/sdca_sparse_bucket.py) — concrete ad-hoc arrays are
    checked host-side here; violating rows must be sanitized with
    `data.formats.zero_duplicates` first.  Unlike the dense wrapper
    there is no silent B/nnz padding: tile alignment is a data-layout
    contract (the cache stores tiles pre-aligned) and misalignment
    raises with the fix spelled out.  Only d is padded (zero rows,
    never indexed).
    """
    if interpret is None:
        interpret = _interpret_default()
    _check_csr_invariant(idx, val, source)
    n_local, nnz = idx.shape
    B = bucket
    if B <= 0 or n_local % B:
        raise ValueError(
            f"bucket={B} must divide the {source} chunk's row count "
            f"{n_local} (the engine hands the kernel whole buckets)")
    d = v0.shape[0]

    idxb = idx.reshape(n_local // B, B, nnz)
    valb = val.reshape(n_local // B, B, nnz)
    yb = yl.reshape(n_local // B, B)
    ab = al.reshape(n_local // B, B)
    # per-row curvature from the scan's own `sdca.row_sq_norms`; the
    # kernel must not recompute it per tile (see
    # sdca_sparse_bucket._kernel on why this is bitwise-load-bearing)
    qb = sdca.row_sq_norms(val.astype(jnp.float32)).reshape(
        n_local // B, B)
    scal = jnp.stack([jnp.float32(lam_n), jnp.float32(sig)])

    a_new, v_fin = sdca_sparse_bucket.sdca_sparse_bucket_kernel(
        obj, idxb, valb, yb, ab, qb, _lane_dense(v0, d), scal, interpret,
        source)

    a_out = a_new.reshape(-1)
    dv = (v_fin.reshape(-1)[:d] - v0.astype(jnp.float32)) \
        / jnp.float32(sig)
    return a_out.astype(al.dtype), dv.astype(v0.dtype)


def sdca_sparse_sharded_subepoch(obj: Objective, idx, val, yl, al, v0,
                                 lam_n, sig, *, bucket: int,
                                 model_axis: str | None = None,
                                 model_lanes: int = 1,
                                 lane=None,
                                 interpret: bool | None = None,
                                 source: str = "ad-hoc arrays"):
    """One LANE's feature-sharded sparse sub-epoch (DESIGN.md S12).

    Call-compatible with `sdca_sparse_bucket_subepoch` plus the model-
    axis knobs: v0 is the (d,) REPLICATED shared vector, but this lane
    keeps only its contiguous `sparse_slice_width(d, model_lanes)` rows
    resident (in VMEM on TPU) and, per bucket, (1) gathers its partial
    working set, (2) all-gathers the partials over `model_axis` and
    keeps the owning lane's bits per entry — pure data movement, so the
    assembled W is BITWISE the replicated kernel's W; a psum of partial
    margins would reorder the sums and break the contract — then
    (3) runs the shared in-bucket recursion and scatters its owned
    entries.  Returns (a_new, dv) with dv the UNSCALED global delta
    whose support is ONLY this lane's slice: the engine's ordered
    model-axis dv sync adds the disjoint slices (plus exact zeros)
    back into the serial dv, entry for entry.

    With model_axis=None the exchange is the identity and `lane`
    (default 0) picks the slice — the single-process form the kernel
    tests drive lane by lane.
    """
    if interpret is None:
        interpret = _interpret_default()
    _check_csr_invariant(idx, val, source)
    n_local, nnz = idx.shape
    B = bucket
    if B <= 0 or n_local % B:
        raise ValueError(
            f"bucket={B} must divide the {source} chunk's row count "
            f"{n_local} (the engine hands the kernel whole buckets)")
    d = v0.shape[0]
    M = max(int(model_lanes), 1)
    d_loc = sparse_slice_width(d, M)
    nb = n_local // B

    if model_axis is not None:
        # audit: collective-ok lane id seeds lo (scanned as xs below)
        lane_ix = jax.lax.axis_index(model_axis).astype(jnp.int32)
    else:
        lane_ix = jnp.int32(0 if lane is None else lane)
    lo0 = lane_ix * jnp.int32(d_loc)

    idxb = idx.reshape(nb, B, nnz)
    valb = val.reshape(nb, B, nnz)
    yb = yl.reshape(nb, B)
    ab = al.reshape(nb, B)
    # per-row curvature at FULL chunk shape — bitwise-load-bearing,
    # exactly as in the replicated wrapper (and replicated over lanes:
    # every lane sees the same q bits the scan uses)
    qb = sdca.row_sq_norms(val.astype(jnp.float32)).reshape(nb, B)
    v_flat = _lane_dense(v0, d_loc * M).reshape(-1)
    v_loc0 = jax.lax.dynamic_slice(v_flat, (lo0,), (d_loc,)).reshape(
        d_loc // sdca_sparse_bucket.LANES, sdca_sparse_bucket.LANES)
    scal = jnp.stack([jnp.float32(lam_n), jnp.float32(sig)])

    # lo rides in the scanned xs, one copy per bucket: a value the loop
    # closes over — or a carry it only forwards, which jax hoists into
    # a closure — is an axis_index-derived loop invariant, the
    # JAX-LOOP-CLOSURE hazard class (see engine.run_epoch's unrolled
    # chunk loop).  Per-iteration inputs keep every lane on its own
    # slice by construction.
    def _step(v_loc, tile):
        idx_t, val_t, y_t, a_t, q_t, lo = tile
        w_loc = sdca_sparse_bucket.sdca_sparse_gather_bucket(
            idx_t, v_loc, lo, interpret, source)
        if model_axis is not None and M > 1:
            # audit: collective-ok all-gather + owner-select (no psum)
            gathered = jax.lax.all_gather(w_loc, model_axis)
            owner = (idx_t // jnp.int32(d_loc)).astype(jnp.int32)
            w = jnp.take_along_axis(gathered, owner[None], axis=0)[0]
        else:
            w = w_loc
        a_new_t, v_loc = sdca_sparse_bucket.sdca_sparse_sharded_bucket(
            obj, idx_t, val_t, y_t, a_t, q_t, w, v_loc, scal, lo,
            interpret, source)
        return v_loc, a_new_t

    v_fin, a_new = jax.lax.scan(
        _step, v_loc0,
        (idxb, valb, yb, ab, qb, jnp.broadcast_to(lo0, (nb,))))

    dv_loc = (v_fin - v_loc0).reshape(-1) / jnp.float32(sig)
    dv = jax.lax.dynamic_update_slice(
        jnp.zeros((d_loc * M,), jnp.float32), dv_loc, (lo0,))[:d]
    return a_new.reshape(-1).astype(al.dtype), dv.astype(v0.dtype)


def rglru_scan(x, a_log, gate_a, gate_x, h0, *, block_t: int = 128,
               interpret: bool | None = None):
    """Blocked RG-LRU linear recurrence; see kernels/rglru.py."""
    if interpret is None:
        interpret = _interpret_default()
    return _rglru.rglru_kernel(x, a_log, gate_a, gate_x, h0,
                               block_t=block_t, interpret=interpret)


def flash_attention(q, k, v, *, kind: str = "causal", window: int = 0,
                    bq: int = 128, bk: int = 128,
                    interpret: bool | None = None):
    """(B, S, H, hd) flash attention via the Pallas kernel.

    Pads Sq/Sk to block multiples and hd to the 128-lane tile; the true
    kv length rides in as a mask bound.  On non-TPU backends callers
    should prefer models.attention.blocked_attention (this wrapper runs
    the kernel in interpret mode there — correct but slow).
    """
    from . import flash_attention as _fa
    if interpret is None:
        interpret = _interpret_default()
    B, Sq, H, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    hd_v = v.shape[-1]
    G = H // Hkv
    bq_ = min(bq, _round_up(Sq, 8))
    bk_ = min(bk, _round_up(Sk, 8))
    sq_p = _round_up(Sq, bq_)
    sk_p = _round_up(Sk, bk_)
    hd_p = _round_up(hd, 128) if not interpret else hd
    hdv_p = _round_up(hd_v, 128) if not interpret else hd_v

    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, hd_v)
    qf = jnp.pad(qf, ((0, 0), (0, sq_p - Sq), (0, hd_p - hd)))
    kf = jnp.pad(kf, ((0, 0), (0, sk_p - Sk), (0, hd_p - hd)))
    vf = jnp.pad(vf, ((0, 0), (0, sk_p - Sk), (0, hdv_p - hd_v)))

    o = _fa.flash_attention_kernel(qf, kf, vf, kind=kind, window=window,
                                   bq=bq_, bk=bk_, group=G, seq_k=Sk,
                                   interpret=interpret)
    o = o[:, :Sq, :hd_v].reshape(B, H, Sq, hd_v)
    return o.transpose(0, 2, 1, 3)
