"""Bucketing: the paper's cache-line locality optimization, re-derived for TPU.

On CPU the paper groups consecutive training examples into buckets sized
by the cache line (8-16 examples) so that the model vector alpha is
accessed with cache-line locality and the per-epoch shuffle permutes
n/B bucket ids instead of n example ids.

On TPU the analogous fast memory is VMEM, and the analogous win is
threefold (see DESIGN.md S2/S6):
  * the (B x d_pad) data tile for one bucket is streamed HBM->VMEM once
    and reused for margins, Gram matrix, and the shared-vector update;
  * the per-epoch shuffle is over n/B bucket ids (device-side);
  * processing a bucket through its Gram matrix turns the memory-bound
    dot/axpy stream into MXU matmuls and (for feature-sharded runs)
    amortizes one model-axis psum over B coordinates instead of one per
    coordinate.

The bucket recursion is EXACTLY equivalent to sequential SDCA over the
bucket's coordinates (the margin evolution within a bucket only depends
on the bucket Gram matrix), so unlike the paper's CPU variant the TPU
bucket costs no extra epochs relative to an unbucketed pass with the
same visiting order; the residual convergence cost is only the reduced
shuffle granularity, identical to the paper's.
"""
from __future__ import annotations

import dataclasses

# The paper: bucket size = cacheline/8B (8 or 16).  TPU: bucket size is
# bounded by VMEM (the (B x d_pad) tile + B x B Gram must fit) and should
# be a multiple of the 8-sublane register shape for the VPU.
DEFAULT_BUCKET = 16
# The paper disables bucketing when the model vector (n entries) fits the
# last-level cache (~500k entries).  TPU analogue: alpha lives in HBM and
# the kernel keeps v resident in VMEM; the shuffle-granularity cost is only
# worth paying when alpha is big enough that random single-coordinate
# access patterns dominate.  Same cut-off, same spirit.
LLC_ENTRIES = 500_000
# VMEM budget we allow one bucket tile to claim (bytes).  v5e VMEM is
# ~128 MiB/core; we stay far below so double-buffering + v + Gram fit.
VMEM_TILE_BUDGET = 4 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    n: int                  # number of examples (padded)
    bucket: int             # examples per bucket (1 = bucketing off)
    n_buckets: int

    @property
    def enabled(self) -> bool:
        return self.bucket > 1


def choose_bucket_size(n: int, d: int, *, dtype_bytes: int = 4,
                       force: int | None = None,
                       llc_entries: int = LLC_ENTRIES) -> int:
    """Run-time bucket-size heuristic (paper S3, adapted to VMEM).

    force=B overrides; force=1 disables.  Otherwise: disabled when alpha
    fits the 'LLC' threshold, else the largest B in {8, 16, 32, 64} whose
    (d x B) tile fits the VMEM tile budget.
    """
    if force is not None:
        return max(1, force)
    if n <= llc_entries:
        return 1
    for b in (64, 32, 16, 8):
        if d * b * dtype_bytes <= VMEM_TILE_BUDGET:
            return b
    return 8


def make_plan(n: int, d: int, **kw) -> BucketPlan:
    b = choose_bucket_size(n, d, **kw)
    if n % b:
        raise ValueError(f"n={n} not divisible by bucket={b}; pad the data")
    return BucketPlan(n=n, bucket=b, n_buckets=n // b)
