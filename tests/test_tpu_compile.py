"""Compile the SDCA Pallas kernels for a described TPU v5e, at real widths.

Nothing runs: the TPU compiler builds each kernel for a chip that is
described, not attached, so Mosaic's lowering rules and VMEM limits are
checked on every PR without a chip.  The topology is described inside a
module fixture (never at import): only one process at a time may load
the TPU library.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import SingleDeviceSharding

from repro.core.objectives import LOGISTIC
from repro.kernels import ops, sdca_bucket, sdca_sparse_bucket as ssb

NB = 16                       # buckets per compiled sub-epoch


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back on the CPU, so
    # keep them out of any persistent compilation cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _dense(sh, d, B):
    dp = sdca_bucket.d_pad(d)
    return (lambda xb, yb, ab, v0, sc: sdca_bucket.sdca_bucket_kernel(
        LOGISTIC, xb, yb, ab, v0, sc, False, "test"),
        _sds(sh, (NB, B, dp)), _sds(sh, (NB, B)), _sds(sh, (NB, B)),
        _sds(sh, (1, dp)), _sds(sh, (2,)))


def _sparse(sh, d, nnz, B):
    rows = ssb.v_pad(d) // ssb.LANES
    return (lambda i, v, y, a, q, v0, sc: ssb.sdca_sparse_bucket_kernel(
        LOGISTIC, i, v, y, a, q, v0, sc, False, "test"),
        _sds(sh, (NB, B, nnz), jnp.int32), _sds(sh, (NB, B, nnz)),
        _sds(sh, (NB, B)), _sds(sh, (NB, B)), _sds(sh, (NB, B)),
        _sds(sh, (rows, ssb.LANES)), _sds(sh, (2,)))


@pytest.mark.parametrize("d", [28, 2000], ids=["higgs", "epsilon"])
@pytest.mark.parametrize("B", [8, 64])
def test_dense_kernel_compiles(one_chip, d, B):
    _compile(*_dense(one_chip, d, B))


@pytest.mark.parametrize("B", [8, 16])
def test_sparse_replicated_kernel_compiles_at_criteo_width(one_chip, B):
    """criteo: v of d=1,000,000 features resident in VMEM, nnz=40."""
    assert ops.sparse_solver_plan(B * NB, 40, 1_000_000, B)[0] \
        == "pallas-replicated"
    _compile(*_sparse(one_chip, 1_000_000, 40, B))


def test_sharded_pair_compiles_at_webspam_slice(one_chip):
    """webspam (d=16,609,143, nnz 3727 padded to 3728) over 8 model
    lanes: one lane's v slice and one bucket of the gather/update
    pair."""
    d, nnz, B, M = 16_609_143, 3728, 8, 8
    assert ops.sparse_solver_plan(B, nnz, d, B, model_lanes=M)[0] \
        == "pallas-sharded"
    rows = ops.sparse_slice_width(d, M) // ssb.LANES
    sh = one_chip
    idx, vals = _sds(sh, (B, nnz), jnp.int32), _sds(sh, (B, nnz))
    row, v_loc = _sds(sh, (B,)), _sds(sh, (rows, ssb.LANES))
    lo = _sds(sh, (), jnp.int32)
    _compile(lambda i, v, l_: ssb.sdca_sparse_gather_bucket(
        i, v, l_, False, "test"), idx, v_loc, lo)
    _compile(lambda i, v, y, a, q, w, vl, sc, l_:
             ssb.sdca_sparse_sharded_bucket(
                 LOGISTIC, i, v, y, a, q, w, vl, sc, l_, False, "test"),
             idx, vals, row, row, row, vals, v_loc, _sds(sh, (2,)), lo)


@pytest.mark.parametrize("nnz,form", [
    (40, (1, 40)),           # criteo: every entry straight-line
    (3728, (466, 8)),        # webspam: a rolled loop of 8-entry blocks
])
def test_sparse_entry_loop_form(nnz, form):
    """The sparse kernel's nnz loops take their form from nnz alone:
    full unroll up to `UNROLL_NNZ`, blocks of `ENTRY_BLOCK` above."""
    assert ssb.entry_loop_form(nnz) == form


@pytest.fixture
def vmem_limit(monkeypatch):
    """set(limit): compile the next kernels under that scoped-VMEM
    limit (traces are cached with their compiler params, so the caches
    are dropped around each change)."""
    orig = pltpu.CompilerParams

    def set_limit(limit):
        jax.clear_caches()
        monkeypatch.setattr(pltpu, "CompilerParams", functools.partial(
            orig, vmem_limit_bytes=int(limit)))

    yield set_limit
    jax.clear_caches()


def test_dense_vmem_estimate_pinned_by_compile(one_chip, vmem_limit):
    """epsilon at B=64: the kernel compiles within the VMEM its
    estimator counts, and not within half of it — the estimate is
    neither short of the compiler's layout nor padded past 2x."""
    est = sdca_bucket.vmem_bytes_estimate(64, 2000)
    vmem_limit(est)
    _compile(*_dense(one_chip, 2000, 64))
    vmem_limit(est // 2)
    with pytest.raises(Exception, match="vmem"):
        _compile(*_dense(one_chip, 2000, 64))


def test_sparse_vmem_estimate_is_enough(one_chip, vmem_limit):
    """criteo at B=16: the replicated sparse kernel compiles within the
    VMEM its estimator counts (the resident 4 MB v included)."""
    vmem_limit(ssb.vmem_bytes_estimate(16, 40, 1_000_000))
    _compile(*_sparse(one_chip, 1_000_000, 40, 16))


def test_resident_mesh_epoch_compiles_for_four_chips(topo):
    """The epoch `Session` runs with four lanes on a v5e:2x2 host, at
    criteo's width (d = 1,000,000, nnz = 40): the sparse kernel on each
    chip, one all-to-all for each re-dealt array (idx, val, y, alpha,
    row ids) and one lane-sum all-reduce a chunk."""
    import numpy as np
    from jax.sharding import AxisType, Mesh
    from repro.launch import glm

    mesh = Mesh(np.array(topo.devices).reshape(1, 4, 1),
                ("pod", "data", "model"), axis_types=(AxisType.Auto,) * 3)
    n, d = 8192, 1_000_000
    sc = glm.GLMScale("criteo-dp4", "sparse", n=n, d=d, nnz=40, bucket=8,
                      chunks=2, compress_pod=False, local_solver="pallas")
    rows, ex, v_sh = glm.resident_shardings(sc, mesh)
    args = (_sds(rows[0], (n, 40), jnp.int32), _sds(rows[1], (n, 40)),
            _sds(ex, (n,)), _sds(ex, (n,), jnp.int32), _sds(ex, (n,)),
            _sds(v_sh, (d,)), jax.ShapeDtypeStruct((), jnp.int32))
    text = _compile(glm.make_sparse_epoch(sc, mesh, LOGISTIC,
                                          interpret=False, row_ids=True),
                    *args)
    assert len(re.findall(r"= \S+ all-to-all\(", text)) == 5
    assert len(re.findall(r"= \S+ all-reduce\(", text)) == 2
