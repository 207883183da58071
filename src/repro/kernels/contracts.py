"""Kernel contract registry: every Pallas entry point's guard rails.

Each live kernel entry point (a module-level function that issues a
``pallas_call``) registers the misfit predicate that routes infeasible
workloads away from it at trace time and the VMEM model that budgets
its footprint.  References are lazy ``"module:attr"`` strings so this
module stays stdlib-importable (the static-analysis lint layer reads
it without jax); `repro.analysis.lint.resolve_contract_refs` import-
checks them, and the LINT-KERNEL-CONTRACT rule fails the build when a
new pallas_call entry point lands unregistered.

Keys are ``<module-stem>.<function-name>``.  Quarantined seed kernels
(flash_attention, rglru — see `repro.analysis.config.QUARANTINE`) are
out of scope: they are not reachable from the solver paths.
"""
from __future__ import annotations

__all__ = ["KERNEL_CONTRACTS"]

KERNEL_CONTRACTS: dict[str, dict[str, str]] = {
    # dense bucket kernel: whole (B, d_pad) tiles + Gram recursion
    "sdca_bucket.sdca_bucket_kernel": {
        "misfit": "repro.kernels.ops:dense_kernel_misfit",
        "vmem_estimate": "repro.kernels.sdca_bucket:vmem_bytes_estimate",
    },
    # sparse replicated kernel: VMEM-resident v over CSR tiles
    "sdca_sparse_bucket.sdca_sparse_bucket_kernel": {
        "misfit": "repro.kernels.ops:sparse_kernel_misfit",
        "vmem_estimate":
            "repro.kernels.sdca_sparse_bucket:vmem_bytes_estimate",
    },
    # sharded-v pair (DESIGN.md S12): both halves of one bucket step
    # share the sharded feasibility predicate + footprint model
    "sdca_sparse_bucket.sdca_sparse_gather_bucket": {
        "misfit": "repro.kernels.ops:sparse_kernel_misfit",
        "vmem_estimate":
            "repro.kernels.sdca_sparse_bucket:vmem_bytes_estimate_sharded",
    },
    "sdca_sparse_bucket.sdca_sparse_sharded_bucket": {
        "misfit": "repro.kernels.ops:sparse_kernel_misfit",
        "vmem_estimate":
            "repro.kernels.sdca_sparse_bucket:vmem_bytes_estimate_sharded",
    },
}
