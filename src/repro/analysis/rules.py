"""The rule registry: every machine-checked invariant, with its ID.

One `Rule` per invariant the auditor enforces.  The registry is the
single source of truth for rule IDs: DESIGN.md S14 and
docs/analysis.md carry a table of these IDs which
``tools/docs_check.py`` keeps in sync, and every mutation self-test
(`analysis.selftest`) names the rule it proves fires.  Stdlib-only on
purpose — docs tooling imports this without jax installed.
"""
from __future__ import annotations

import dataclasses

__all__ = [
    "Rule", "Finding", "RULES",
    "JAX_PSUM_EXCHANGE", "JAX_LOOP_CLOSURE", "JAX_NONDET_PRIM",
    "LINT_KERNEL_CONTRACT", "LINT_RAW_COLLECTIVE", "LINT_UNSEEDED_RNG",
    "LINT_CSR_ENTRY", "LINT_BARE_EXCEPT", "VMEM_PLAN_BUDGET",
]

JAX_PSUM_EXCHANGE = "JAX-PSUM-EXCHANGE"
JAX_LOOP_CLOSURE = "JAX-LOOP-CLOSURE"
JAX_NONDET_PRIM = "JAX-NONDET-PRIM"
LINT_KERNEL_CONTRACT = "LINT-KERNEL-CONTRACT"
LINT_RAW_COLLECTIVE = "LINT-RAW-COLLECTIVE"
LINT_UNSEEDED_RNG = "LINT-UNSEEDED-RNG"
LINT_CSR_ENTRY = "LINT-CSR-ENTRY"
LINT_BARE_EXCEPT = "LINT-BARE-EXCEPT"
VMEM_PLAN_BUDGET = "VMEM-PLAN-BUDGET"


@dataclasses.dataclass(frozen=True)
class Rule:
    """One machine-checked invariant.

    ``layer`` is where the checker lives ("jaxpr" | "lint" | "budget");
    ``invariant`` states the contract being enforced; ``history`` names
    the concrete bug (or bug class) it guards against — the rule table
    in DESIGN.md S14 renders these three columns verbatim.
    """
    id: str
    layer: str
    invariant: str
    history: str


RULES: dict[str, Rule] = {r.id: r for r in (
    Rule(
        JAX_PSUM_EXCHANGE, "jaxpr",
        "Under deterministic=True no cross-lane sum-reordering "
        "reduction (psum / psum_scatter) may appear anywhere in a "
        "traced epoch program: every exchange on the contract path is "
        "all-gather + an ordered jnp.sum (or pure data movement).",
        "The sharded sparse working-set exchange (DESIGN.md S12) was "
        "designed as all-gather + owner-select precisely because a "
        "psum of partial margins reorders float sums and silently "
        "breaks the bitwise sim<->mesh contract."),
    Rule(
        JAX_LOOP_CLOSURE, "jaxpr",
        "Inside a shard_map region, no scan/while/fori_loop may close "
        "over a loop-invariant integer value derived from "
        "lax.axis_index (it must ride in the scanned xs; jax 0.9 "
        "hoists a carry the body only forwards into a const): "
        "shard_map may treat such closures as replicated, and every "
        "lane then runs lane 0's value.",
        "PR 1: a fori_loop chunk loop replicated lane 0's visit perm "
        "to every lane (now statically unrolled in engine.run_epoch); "
        "PR 6: the sharded sparse solver threaded its slice offset "
        "`lo` through the scan carry, which jax 0.9 hoists back into "
        "a const, so it now rides in the scanned xs."),
    Rule(
        JAX_NONDET_PRIM, "jaxpr",
        "Under deterministic=True no other unordered cross-lane "
        "reduction primitive (pmax / pmin / reduce_scatter) may be "
        "reachable: the contract's reductions are all enumerated, "
        "ordered gather-sums.",
        "Guards the same bug class as JAX-PSUM-EXCHANGE for the "
        "collectives that do not spell 'psum' — a reduce_scatter "
        "sneaking into a sync path would reorder sums identically."),
    Rule(
        LINT_KERNEL_CONTRACT, "lint",
        "Every Pallas kernel entry point in the live kernels must be "
        "registered in kernels/contracts.py with a misfit predicate "
        "and a vmem_bytes_estimate* model, so trace-time routing and "
        "the planner can never meet an unbudgeted kernel.",
        "PR 4's review rounds: kernels without misfit predicates "
        "failed at epoch build (or as opaque Mosaic OOMs) instead of "
        "routing to the XLA path at trace time."),
    Rule(
        LINT_RAW_COLLECTIVE, "lint",
        "core/engine.py and kernels/ops.py may call lax collectives "
        "(psum, all_gather, all_to_all, psum_scatter, ppermute, "
        "axis_index) only on lines carrying an explicit "
        "'# audit: collective-ok' marker: every cross-lane exchange "
        "is an enumerated, reviewed site.",
        "The determinism contract is a property of a closed set of "
        "exchange sites; an unmarked collective added in review is "
        "exactly how an unordered reduction slips onto the contract "
        "path."),
    Rule(
        LINT_UNSEEDED_RNG, "lint",
        "No live module may use numpy's global-state RNG "
        "(np.random.rand & co.) or the stdlib random module: all "
        "randomness flows from explicit seeds "
        "(np.random.default_rng(seed), jax.random keys).",
        "The repro's schedules, synthetic datasets and re-deals are "
        "all replayable from (seed, epoch); one unseeded draw makes "
        "a training run unreproducible."),
    Rule(
        LINT_CSR_ENTRY, "lint",
        "Each CSR entry altitude (kernels/ops.py, api/session.py) "
        "must call data.formats.raise_on_duplicate_nonzeros: rows "
        "with duplicate nonzero feature ids silently break the "
        "sparse kernel's bitwise-vs-XLA contract.",
        "PR 4 review rounds added the check at both altitudes after "
        "duplicate synthetic rows broke the bitwise contract; losing "
        "either call reopens the hole for ad-hoc arrays."),
    Rule(
        LINT_BARE_EXCEPT, "lint",
        "No live module may contain a bare `except:` or an `except "
        "Exception/BaseException` handler that swallows the error "
        "(no re-raise) without an explicit '# audit: except-ok' "
        "marker: every swallow site is an enumerated, reviewed "
        "recovery decision, and injected faults must surface through "
        "the typed resilience layer instead of dying silently.",
        "PR 9's fault-injection campaign: recovery machinery is built "
        "on typed errors (TileCorruptionError, FaultInjectedIOError) "
        "and a BaseException crash sentinel; one anonymous "
        "`except Exception: pass` between the fault site and the "
        "resilience layer turns a recoverable fault into silent "
        "state corruption."),
    Rule(
        VMEM_PLAN_BUDGET, "budget",
        "No plan the planner can emit (any candidate geometry over "
        "any registry workload x topology) may claim a pallas route "
        "whose kernel VMEM estimate exceeds TOTAL_VMEM_BUDGET_BYTES "
        "or whose resident vector busts V_VMEM_BUDGET_BYTES.",
        "Pre-PR-4 wide tiles (e.g. B=16, nnz=512) surfaced as opaque "
        "Mosaic OOMs at run time; the budget sweep fails the same "
        "geometry offline, before a TPU ever sees it."),
)}


@dataclasses.dataclass
class Finding:
    """One rule violation: rule ID + anchor + human message.

    ``where`` is a file:line anchor when the checker has one (lint
    rules always do; jaxpr rules carry the eqn's source_info summary);
    ``case`` labels the audit-matrix case or self-test that produced
    it (e.g. "webspam/pallas-sharded/det").
    """
    rule: str
    message: str
    where: str = ""
    case: str = ""

    def to_json(self) -> dict:
        """JSON-safe dict for the machine-readable report."""
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        loc = f" [{self.where}]" if self.where else ""
        case = f" ({self.case})" if self.case else ""
        return f"{self.rule}{case}{loc}: {self.message}"
