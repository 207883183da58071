"""Static analysis of the determinism + VMEM contracts (DESIGN.md S14).

Two layers, one report:

* **Layer 1 — jaxpr auditor** (`jaxpr_audit`, `matrix`): abstract-trace
  the real epoch programs (the same `launch/glm.py` shard_map builds
  and `engine.make_streamed_step` steps that training runs) for every
  registry workload x solver route, then walk the ClosedJaxprs for
  contract violations — sum-reordering collectives on exchanges the
  determinism contract requires to be ordered, and the shard_map
  loop-invariant-replicated closure hazard (rule IDs in `rules`).
* **Layer 2 — repo lint + budget audit** (`lint`, `budget`): AST rules
  ruff cannot express (kernel-contract registration, collective
  allowlist markers, unseeded RNG, CSR-invariant altitudes) plus an
  offline sweep proving no plan `core.planner.resolve_plan` resolves
  over a grid of geometries busts the kernels' VMEM budgets.

`runner.run_audit` orchestrates both and emits the machine-readable
report; `selftest.run_selftests` mutates each invariant and proves the
matching detector fires.  Front door: ``tools/audit.py``.

This ``__init__`` stays import-light (no jax): `rules`, `config`, and
`lint` are stdlib-only so docs tooling can read the rule registry
without an accelerator stack.
"""
from . import config, rules           # noqa: F401  (stdlib-only)
from .rules import RULES, Finding, Rule  # noqa: F401
