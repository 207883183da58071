"""Pallas TPU kernel: RG-LRU gated linear recurrence (Griffin / RecurrentGemma).

    r_t = sigmoid(gate_a_t);  i_t = sigmoid(gate_x_t)
    a_t = exp(c * a_log * r_t),  c = 8
    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)

TPU blocking: grid over time blocks; the hidden state h (1 x D tile)
stays VMEM-resident across grid steps (aliased accumulator, "arbitrary"
semantics), each grid step streams a (block_t x D) slab of x/gates
HBM->VMEM, fuses the gate math, and walks the recurrence with D-wide VPU
ops.  This is the same "pin the sequential hot state in fast memory,
stream the bulk data in blocks" shape as the sdca_bucket kernel — the
paper's central systems idea applied to the recurrence that makes the
hybrid/SSM architectures sub-quadratic at 500k context.

D must be a multiple of 128 (lane tile); block_t a multiple of 8.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_C = 8.0


def _kernel(x_ref, ga_ref, gx_ref, alog_ref, h0_ref, out_ref, h_ref):
    first = pl.program_id(0) == 0

    @pl.when(first)
    def _():
        h_ref[...] = h0_ref[...].astype(jnp.float32)

    x = x_ref[...].astype(jnp.float32)          # (bt, D)
    ga = ga_ref[...].astype(jnp.float32)
    gx = gx_ref[...].astype(jnp.float32)
    alog = alog_ref[...].astype(jnp.float32)    # (1, D)

    r = jax.nn.sigmoid(ga)
    i = jax.nn.sigmoid(gx)
    log_a = _C * alog * r                        # (bt, D), alog broadcasts
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (i * x)

    bt = x.shape[0]

    def body(t, carry):
        h, out = carry
        at = jax.lax.dynamic_slice_in_dim(a, t, 1, axis=0)   # (1, D)
        bt_ = jax.lax.dynamic_slice_in_dim(b, t, 1, axis=0)
        h = at * h + bt_
        out = jax.lax.dynamic_update_slice_in_dim(out, h, t, axis=0)
        return h, out

    h, out = jax.lax.fori_loop(
        0, bt, body, (h_ref[...], jnp.zeros_like(x)))
    out_ref[...] = out.astype(out_ref.dtype)
    h_ref[...] = h


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def rglru_kernel(x, a_log, gate_a, gate_x, h0, *, block_t: int = 128,
                 interpret: bool = False):
    """x, gate_a, gate_x: (T, D); a_log: (D,); h0: (D,) -> h: (T, D)."""
    T, D = x.shape
    if T % block_t:
        raise ValueError(f"T={T} must divide by block_t={block_t}")
    if D % 128 and not interpret:
        raise ValueError(f"D={D} must be a multiple of 128 on TPU")
    grid = (T // block_t,)

    out, _ = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, D), lambda i: (i, 0)),
            pl.BlockSpec((block_t, D), lambda i: (i, 0)),
            pl.BlockSpec((block_t, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, D), x.dtype),
            jax.ShapeDtypeStruct((1, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x, gate_a, gate_x, a_log.reshape(1, D), h0.reshape(1, D))
    return out
