"""Pallas TPU kernel: one worker's bucketed SDCA sub-epoch.

This is the paper's cache-line bucket, re-blocked for the TPU memory
hierarchy (DESIGN.md S2/S6):

  * the shared-vector replica v, a lane-dense (1, d_pad) row, is
    pinned in VMEM for the whole sub-epoch: copied in from HBM at the
    first bucket, written back once after the last (input/output
    aliasing + a constant index map) — the VMEM analogue of the paper
    keeping the hot state cache-resident;
  * each grid step streams ONE bucket tile X_b (B x d_pad, one example
    per row) HBM->VMEM and uses it three times (margins, Gram,
    v-update) — one HBM pass where the unbucketed algorithm does B
    strided passes;
  * margins + Gram go through the MXU (two matmuls), the in-bucket
    recursion is O(B^2) scalar work on VMEM-resident vectors: Gram rows
    are read from a VMEM scratch with `pl.ds`, single entries of a
    row by an iota-mask select (Mosaic has no dynamic slicing of
    values).

Grid is 1-D over buckets with "arbitrary" dimension semantics: buckets
are processed IN ORDER, which is what makes the kernel equivalent to
sequential SDCA over the same visiting order.

B must be a multiple of 8 (f32 sublane tile) and <= 512; d_pad a
multiple of 128 (lane tile).  Zero-padded features are harmless (they
contribute 0 to every inner product).  Scalars (lam*n, sigma') ride in
SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.objectives import Objective
from .sdca_sparse_bucket import (LANES, TOTAL_VMEM_BUDGET_BYTES, _lane,
                                 _round_up, _row_spec, _tile_bytes)

Array = jax.Array

#: Largest bucket the in-bucket Gram recursion supports (docstring
#: contract above; beyond this the (B, B) Gram + serial recursion stop
#: paying for themselves anyway).
MAX_BUCKET = 512

_HIGHEST = jax.lax.Precision.HIGHEST


def d_pad(d: int) -> int:
    """Features a bucket tile's rows (and v) are padded to."""
    return _round_up(max(int(d), 1), LANES)


def vmem_bytes_estimate(B: int, d: int) -> int:
    """VMEM footprint of the kernel, as the compiler lays it out: the
    double-buffered (B, d_pad) bucket tile, the one resident (1, d_pad)
    v, the double-buffered (1, B) y/alpha/alpha-out rows, the (B, B)
    Gram scratch, and the body's values — up to four more tile-sized
    operands of the full-precision f32 matmuls plus the Gram before it
    is stored (pinned against a v5e compile in
    tests/test_tpu_compile.py).  Shared with `ops.dense_kernel_misfit`
    so the "auto" path can pre-check static shapes and fall back
    instead of raising."""
    tile = _tile_bytes(B, d_pad(d))
    return (6 * tile + _tile_bytes(1, d_pad(d))
            + 3 * 2 * _tile_bytes(1, B) + 2 * _tile_bytes(B, B))


def _nt(a, b):
    """a @ b.T at full f32 precision."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(obj: Objective, x_ref, y_ref, a_ref, scal_ref, v_hbm,
            aout_ref, v_ref, g_ref):
    """Body for one bucket (one grid step)."""
    # v lives in the aliased output block; copy it in from HBM once
    @pl.when(pl.program_id(0) == 0)
    def _():
        pltpu.sync_copy(v_hbm, v_ref)

    x = x_ref[...].astype(jnp.float32)          # (B, d_pad)
    y = y_ref[...].astype(jnp.float32)          # (1, B)
    a0 = a_ref[...].astype(jnp.float32)         # (1, B)
    lam_n = scal_ref[0]
    sig = scal_ref[1]
    v = v_ref[...]                              # (1, d_pad) f32

    m0 = _nt(v, x)                              # (1, B)  MXU
    g_ref[...] = _nt(x, x)                      # (B, B)  MXU
    B = m0.shape[1]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, B), 1)

    def body(i, carry):
        m, deltas = carry
        grow = g_ref[pl.ds(i, 1), :]            # (1, B)
        q = sig * _lane(grow, i) / lam_n
        d = obj.delta(_lane(m, i), _lane(a0, i), _lane(y, i), q)
        m = m + (sig * d / lam_n) * grow
        return m, jnp.where(lanes == i, d, deltas)

    _, deltas = jax.lax.fori_loop(0, B, body, (m0, jnp.zeros_like(m0)))

    v_ref[...] = v + (sig / lam_n) * jnp.dot(
        deltas, x, precision=_HIGHEST, preferred_element_type=jnp.float32)
    aout_ref[...] = (a0 + deltas).astype(aout_ref.dtype)


@functools.partial(jax.jit, static_argnums=(0, 6, 7))
def sdca_bucket_kernel(obj: Objective, xb: Array, yb: Array, ab: Array,
                       v0: Array, scal: Array, interpret: bool = False,
                       source: str = "ad-hoc arrays"
                       ) -> tuple[Array, Array]:
    """Run the sub-epoch kernel.

    xb: (nb, B, d_pad) bucket tiles in visiting order, one example per
    row; yb, ab: (nb, B);  v0: (1, d_pad) f32;  scal: (2,) f32 =
    [lam*n, sigma'].  Returns (a_new (nb, B), v_final (1, d_pad)).
    v_final includes the sigma'-scaled local evolution (callers unscale
    the global delta).  `source` names where the tiles came from (tile
    cache vs ad-hoc arrays) so alignment errors point at the right fix.
    """
    nb, B, dp = xb.shape
    if dp % LANES or B % 8:
        raise ValueError(
            f"dense bucket tiles from {source} have (B={B}, "
            f"d_pad={dp}); the Pallas kernel needs them to be "
            f"multiples of 8 and {LANES} (the f32 tile).  Fix: "
            f"rebuild the tile cache at an aligned bucket size for "
            f"cached tiles, or route ad-hoc arrays through "
            f"ops.sdca_bucket_subepoch (it zero-pads d and B "
            f"automatically).")
    if B > MAX_BUCKET:
        raise ValueError(
            f"dense bucket tiles from {source} have B={B}; the kernel's "
            f"in-bucket Gram recursion supports B <= {MAX_BUCKET}.  Use "
            f"a smaller bucket, or local_solver='xla'.")
    need = vmem_bytes_estimate(B, dp)
    if need > TOTAL_VMEM_BUDGET_BYTES:
        raise ValueError(
            f"dense bucket tiles from {source} with (d_pad={dp}, "
            f"B={B}) need ~{need} bytes of VMEM (double-buffered tile "
            f"+ resident v + Gram), over the kernel's "
            f"{TOTAL_VMEM_BUDGET_BYTES}-byte total budget.  Use "
            f"local_solver='xla' (HBM-resident v) for this workload, "
            f"shard features, or shrink the bucket.")

    a_new, v_fin = pl.pallas_call(
        functools.partial(_kernel, obj),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((None, B, dp), lambda i: (i, 0, 0)),
            _row_spec(B),
            _row_spec(B),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            _row_spec(B),
            pl.BlockSpec((1, dp), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, 1, B), ab.dtype),
            jax.ShapeDtypeStruct((1, dp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((B, B), jnp.float32)],
        input_output_aliases={4: 1},   # v0 buffer reused as v_final
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(xb, yb[:, None], ab[:, None], scal, v0)
    return a_new[:, 0], v_fin
