"""Audit scope + repo-specific constants (stdlib-only).

The quarantine list is the single place that says which packages are
inert seed scaffolding vs live solver code: the lint layer and ruff
(pyproject.toml ``extend-exclude`` — kept in sync by
tests/test_analysis.py) both skip quarantined paths so findings are
signal, not seed noise.  README.md documents the split.
"""
from __future__ import annotations

import pathlib

#: Repo root (…/src/repro/analysis/config.py -> repo).
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]

#: Inert seed scaffolding, excluded from the audit AND from ruff
#: (pyproject.toml mirrors this list).  `optim/compression.py` is NOT
#: here — the engine's int8 wire compression imports it — so only the
#: unused optimizers are quarantined, not the package.
QUARANTINE = (
    "src/repro/models",
    "src/repro/configs",
    "src/repro/optim/adamw.py",
    "src/repro/optim/lbfgs.py",
    "src/repro/kernels/flash_attention.py",
    "src/repro/kernels/rglru.py",
    "src/repro/kernels/ref.py",
)

#: Where live python sources are discovered for the repo-wide lint
#: rules (unseeded RNG).  Tests/benchmarks/examples are out of scope:
#: they are allowed ad-hoc randomness and are not shipped solver code.
LINT_ROOTS = ("src/repro",)

#: Files whose collective calls must carry the allowlist marker
#: (LINT-RAW-COLLECTIVE).  These are the only modules allowed to issue
#: raw lax collectives at all; everything else under src/repro goes
#: through them.
COLLECTIVE_SCOPED_FILES = (
    "src/repro/core/engine.py",
    "src/repro/kernels/ops.py",
)

#: The allowlist marker a collective call line (or the line above it)
#: must carry, with a short justification after it:
#:     dv = jax.lax.psum(dv, ax)  # audit: collective-ok unordered ...
ALLOWLIST_MARKER = "audit: collective-ok"

#: lax attribute names that count as collectives for the marker rule.
#: axis_index is included deliberately: it is the taint seed of the
#: loop-closure hazard, so every site must be an enumerated one.
COLLECTIVE_CALL_NAMES = frozenset({
    "psum", "psum_scatter", "pmax", "pmin", "pmean", "all_gather",
    "all_to_all", "ppermute", "pshuffle", "axis_index",
})

#: The marker an error-swallowing broad except handler must carry
#: (LINT-BARE-EXCEPT), on the ``except`` line or the line above, with
#: a short justification after it:
#:     except Exception:   # audit: except-ok stale plan cache entry
EXCEPT_MARKER = "audit: except-ok"

#: Files that must each contain a raise_on_duplicate_nonzeros call —
#: the CSR no-duplicate-nonzero invariant's entry altitudes
#: (LINT-CSR-ENTRY).
CSR_ENTRY_FILES = (
    "src/repro/kernels/ops.py",
    "src/repro/api/session.py",
)
CSR_CHECK_NAME = "raise_on_duplicate_nonzeros"

#: Live kernel modules whose pallas_call entry points must be
#: registered in kernels/contracts.py (LINT-KERNEL-CONTRACT).
LIVE_KERNEL_FILES = (
    "src/repro/kernels/sdca_bucket.py",
    "src/repro/kernels/sdca_sparse_bucket.py",
)

# --- jaxpr-layer primitive sets ------------------------------------------

#: Sum-reordering cross-lane reductions: banned anywhere in a
#: deterministic=True trace (JAX-PSUM-EXCHANGE).  lax.psum_scatter
#: binds the "reduce_scatter" primitive; "psum2" is the name lax.psum
#: binds under shard_map's replication checking.
PSUM_PRIMS = frozenset({"psum", "psum2", "reduce_scatter"})

#: Other unordered cross-lane reductions with no ordered twin in the
#: contract (JAX-NONDET-PRIM under deterministic=True).
NONDET_PRIMS = frozenset({"pmax", "pmin"})

#: Pure data-movement collectives, always allowed (documented here so
#: the walker's allow-list is explicit): all_gather, all_to_all,
#: ppermute, pshuffle, axis_index.


def is_quarantined(path) -> bool:
    """True when `path` (absolute or repo-relative) is seed scaffolding."""
    p = pathlib.Path(path)
    if p.is_absolute():
        try:
            p = p.relative_to(REPO_ROOT)
        except ValueError:
            return False
    s = str(p)
    return any(s == q or s.startswith(q + "/") for q in QUARANTINE)
