"""Pallas TPU kernel: flash attention (online-softmax, causal block skip).

The dry-run baselines show the jnp blocked-attention path is the memory
bottleneck of every full-attention train/prefill cell: its f32 score
tensors are HLO-level buffers (e.g. 25.6 s/step of HBM time on
minicpm3-4b train_4k vs 1.19 s of compute).  This kernel is the TPU
answer (DESIGN.md S2's "the kernel IS the locality policy"):

  * grid = (B*H, n_q_blocks, n_kv_blocks), kv innermost with
    "arbitrary" semantics; the (m, l, acc) online-softmax state lives in
    VMEM scratch across the kv sweep — score tiles NEVER touch HBM;
  * causal/local masking is applied at tile granularity, and tiles that
    are fully masked are SKIPPED (pl.when on block indices): causal
    attention does ~half the work the jnp path does;
  * GQA folds q-heads into the batch grid dim; the kv BlockSpec maps
    q-head h to kv-head h // (H // Hkv), so MQA/GQA reuse kv tiles.

Validated in interpret mode against ref.flash_attention_ref over
shape/dtype/mask sweeps (tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            kind: str, window: int, bq: int, bk: int, seq_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    q_start = qi * bq
    k_start = ki * bk

    # tile-level skip: causal/local tiles entirely above the diagonal
    # (or beyond the window) are never computed
    if kind == "causal":
        run = k_start <= q_start + bq - 1
    elif kind == "local":
        run = (k_start <= q_start + bq - 1) & \
              (k_start + bk - 1 >= q_start - window + 1)
    else:
        run = True

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(run)
    def _tile():
        q = q_ref[0].astype(jnp.float32)          # (bq, hd)
        k = k_ref[0].astype(jnp.float32)          # (bk, hd)
        v = v_ref[0].astype(jnp.float32)          # (bk, hd_v)
        s = q @ k.T * (q.shape[-1] ** -0.5)       # (bq, bk)  MXU

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = kpos < seq_k                         # kv padding
        if kind == "causal":
            ok &= qpos >= kpos
        elif kind == "local":
            ok &= (qpos >= kpos) & (qpos - kpos < window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + p @ v
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "kind", "window", "bq", "bk", "group", "seq_k", "interpret"))
def flash_attention_kernel(q, k, v, *, kind: str = "causal",
                           window: int = 0, bq: int = 128, bk: int = 128,
                           group: int = 1, seq_k: int = 0,
                           interpret: bool = False):
    """q: (BH, Sq, hd); k/v: (BHkv, Sk_pad, hd/hd_v); BH = BHkv * group.

    seq_k: true (unpadded) kv length.  Returns (BH, Sq, hd_v).
    """
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    hd_v = v.shape[-1]
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, Sk, bq, bk)
    grid = (BH, Sq // bq, Sk // bk)
    seq_k = seq_k or Sk

    return pl.pallas_call(
        functools.partial(_kernel, kind=kind, window=window, bq=bq,
                          bk=bk, seq_k=seq_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j, g=group:
                         (b // g, j, 0)),
            pl.BlockSpec((1, bk, hd_v), lambda b, i, j, g=group:
                         (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd_v), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd_v), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),      # m
            pltpu.VMEM((bq, 1), jnp.float32),      # l
            pltpu.VMEM((bq, hd_v), jnp.float32),   # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
