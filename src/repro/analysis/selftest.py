"""Mutation self-tests: prove every detector actually fires.

Each check injects one synthetic bug — a probe program traced for the
jaxpr layer, a synthesized source file for the lint layer, a forged
plan for the budget layer — runs it through the EXACT production
checker, and asserts the expected rule ID (and, where meaningful, that
the corrected twin passes: a detector that fires on everything is as
useless as one that fires on nothing).  `tools/audit.py --selftest`
runs these in CI next to the clean-tree audit, so a refactor that
silently lobotomizes a detector fails the build instead of shipping a
green-but-blind auditor.
"""
from __future__ import annotations

import textwrap
from typing import Callable

from . import budget, jaxpr_audit, lint, rules

__all__ = ["run_selftests", "SELFTESTS"]


class SelfTestError(AssertionError):
    """One mutation was not detected (or a clean twin was flagged)."""


def _expect(findings, rule: str, ctx: str) -> None:
    got = [f.rule for f in findings]
    if rule not in got:
        raise SelfTestError(
            f"{ctx}: expected {rule} to fire, got {got or 'nothing'}")


def _expect_clean(findings, ctx: str) -> None:
    if findings:
        raise SelfTestError(
            f"{ctx}: expected no findings, got "
            f"{[str(f) for f in findings]}")


# --- jaxpr layer ----------------------------------------------------------


def _mesh():
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh(pod=1, data=2, model=1)


def _shmap(inner, out_spec=None):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core.engine import shard_map
    f = shard_map(inner, _mesh(), P("data"),
                  out_spec if out_spec is not None else P("data"))
    return jax.make_jaxpr(f)(jnp.zeros(8))


def check_psum_exchange() -> None:
    """Injected psum exchange -> JAX-PSUM-EXCHANGE (det only)."""
    import jax
    from jax.sharding import PartitionSpec as P
    j = _shmap(lambda x: jax.lax.psum(x, "data"), out_spec=P(None))
    _expect(jaxpr_audit.audit_jaxpr(j, deterministic=True),
            rules.JAX_PSUM_EXCHANGE, "psum under deterministic=True")
    _expect_clean(jaxpr_audit.audit_jaxpr(j, deterministic=False),
                  "psum under deterministic=False")


def check_loop_closure() -> None:
    """Un-threaded tainted int in a fori body -> JAX-LOOP-CLOSURE; the
    xs-threaded twin of the same program must pass (this pair is the
    PR 1 / PR 6 bug class reconstructed minimally — the regression
    test pins it too)."""
    import jax

    def buggy(x):
        lane = jax.lax.axis_index("data")
        lo = lane * 4                       # tainted int32 ...
        def body(i, acc):
            return acc + x[lo + i]          # ... closed over: replicated
        return jax.lax.fori_loop(0, 4, body, 0.0)[None]

    def threaded(x):
        import jax.numpy as jnp
        lane = jax.lax.axis_index("data")
        lo = lane * 4
        def body(acc, lo_i):                # a per-iteration input
            return acc + x[lo_i], None
        return jax.lax.scan(body, 0.0, lo + jnp.arange(4))[0][None]

    _expect(jaxpr_audit.audit_jaxpr(_shmap(buggy), deterministic=True),
            rules.JAX_LOOP_CLOSURE, "closed-over axis-derived offset")
    _expect_clean(
        jaxpr_audit.audit_jaxpr(_shmap(threaded), deterministic=True),
        "xs-threaded twin")


def check_nondet_prim() -> None:
    """Injected pmax reduction -> JAX-NONDET-PRIM (det only)."""
    import jax
    from jax.sharding import PartitionSpec as P
    j = _shmap(lambda x: jax.lax.pmax(x, "data"), out_spec=P(None))
    _expect(jaxpr_audit.audit_jaxpr(j, deterministic=True),
            rules.JAX_NONDET_PRIM, "pmax under deterministic=True")
    _expect_clean(jaxpr_audit.audit_jaxpr(j, deterministic=False),
                  "pmax under deterministic=False")


# --- budget layer ---------------------------------------------------------


def check_plan_budget() -> None:
    """Forged over-budget pallas plan -> VMEM-PLAN-BUDGET; the same
    geometry routed honestly (through `planner.resolve_plan`) passes."""
    from repro.core.planner import SolverPlan, resolve_plan
    # (B=512, nnz=2048): the double-buffered idx/val tiles alone are
    # 2*2*512*2048*4 B = 16 MiB
    shape = dict(sparse=True, n=4096, d=64, nnz=2048)
    forged = SolverPlan(solver="pallas", route="pallas-replicated",
                        bucket=512, chunks=1, nnz_multiple=0,
                        feature_shard=False)
    _expect(budget.audit_plan(forged, **shape, name="selftest-forged"),
            rules.VMEM_PLAN_BUDGET, "forged over-budget plan")
    honest = resolve_plan(**shape, bucket=512, backend="tpu")
    _expect_clean(budget.audit_plan(honest, **shape),
                  f"honestly routed plan ({honest.route})")


# --- lint layer -----------------------------------------------------------


_UNREGISTERED_KERNEL = textwrap.dedent("""\
    from jax.experimental import pallas as pl

    def rogue_kernel(x):
        return pl.pallas_call(lambda r, o: None, out_shape=x)(x)
    """)

_UNMARKED_COLLECTIVE = textwrap.dedent("""\
    import jax

    def exchange(dv, ax):
        bad = jax.lax.psum(dv, ax)
        good = jax.lax.all_gather(dv, ax)  # audit: collective-ok test
        return bad + good
    """)

_UNSEEDED_RNG = textwrap.dedent("""\
    import numpy as np

    def jitter(shape):
        good = np.random.default_rng(0).normal(size=shape)
        return good + np.random.rand(*shape)
    """)

_SWALLOWED_EXCEPT = textwrap.dedent("""\
    def load(path):
        try:
            return open(path).read()
        except Exception:
            return None
    """)

_MARKED_EXCEPT = textwrap.dedent("""\
    def load(path):
        try:
            return open(path).read()
        # audit: except-ok missing file means empty payload, by design
        except Exception:
            return None

    def narrow(path):
        try:
            return open(path).read()
        except Exception as e:
            raise RuntimeError(path) from e
    """)


def check_kernel_contract() -> None:
    """Synthesized pallas_call entry point that is not in
    KERNEL_CONTRACTS -> LINT-KERNEL-CONTRACT; the real registered
    kernel files stay clean."""
    from repro.analysis import config
    from repro.kernels.contracts import KERNEL_CONTRACTS
    path = "src/repro/kernels/rogue.py"
    got = lint.check_kernel_contracts(path, _UNREGISTERED_KERNEL,
                                      KERNEL_CONTRACTS)
    _expect(got, rules.LINT_KERNEL_CONTRACT, "unregistered pallas_call")
    for real in config.LIVE_KERNEL_FILES:
        src = (config.REPO_ROOT / real).read_text()
        _expect_clean(
            lint.check_kernel_contracts(real, src, KERNEL_CONTRACTS),
            f"registered kernels in {real}")


def check_raw_collective() -> None:
    """Unmarked lax.psum in a collective-scoped file ->
    LINT-RAW-COLLECTIVE; the marked all_gather beside it passes."""
    path = "src/repro/core/engine.py"     # scoped path, injected source
    got = lint.check_collective_markers(path, _UNMARKED_COLLECTIVE)
    _expect(got, rules.LINT_RAW_COLLECTIVE, "unmarked lax.psum")
    if len(got) != 1:
        raise SelfTestError(
            f"marked all_gather must NOT be flagged; got "
            f"{[str(f) for f in got]}")


def check_unseeded_rng() -> None:
    """np.random.rand global-state draw -> LINT-UNSEEDED-RNG; the
    seeded default_rng draw beside it passes."""
    got = lint.check_unseeded_rng("src/repro/x.py", _UNSEEDED_RNG)
    _expect(got, rules.LINT_UNSEEDED_RNG, "np.random.rand")
    if len(got) != 1:
        raise SelfTestError(
            f"seeded default_rng must NOT be flagged; got "
            f"{[str(f) for f in got]}")


def check_bare_except() -> None:
    """Error-swallowing `except Exception` -> LINT-BARE-EXCEPT; the
    marked twin and the re-raising handler both pass, and a bare
    `except:` fires regardless of markers."""
    path = "src/repro/x.py"
    _expect(lint.check_bare_except(path, _SWALLOWED_EXCEPT),
            rules.LINT_BARE_EXCEPT, "swallowing except Exception")
    _expect_clean(lint.check_bare_except(path, _MARKED_EXCEPT),
                  "marked swallow + re-raising handler")
    bare = _SWALLOWED_EXCEPT.replace("except Exception:", "except:")
    _expect(lint.check_bare_except(path, bare),
            rules.LINT_BARE_EXCEPT, "bare except")


def check_csr_entry() -> None:
    """CSR altitude file stripped of raise_on_duplicate_nonzeros ->
    LINT-CSR-ENTRY."""
    from repro.analysis import config
    stripped = {p: "def nothing():\n    pass\n"
                for p in config.CSR_ENTRY_FILES}
    _expect(lint.check_csr_entries(stripped), rules.LINT_CSR_ENTRY,
            "stripped CSR check")
    live = {p: (config.REPO_ROOT / p).read_text()
            for p in config.CSR_ENTRY_FILES}
    _expect_clean(lint.check_csr_entries(live), "live CSR altitudes")


#: name -> check, one per rule ID (closure check covers the
#: regression-pinned pair).
SELFTESTS: dict[str, Callable[[], None]] = {
    rules.JAX_PSUM_EXCHANGE: check_psum_exchange,
    rules.JAX_LOOP_CLOSURE: check_loop_closure,
    rules.JAX_NONDET_PRIM: check_nondet_prim,
    rules.VMEM_PLAN_BUDGET: check_plan_budget,
    rules.LINT_KERNEL_CONTRACT: check_kernel_contract,
    rules.LINT_RAW_COLLECTIVE: check_raw_collective,
    rules.LINT_UNSEEDED_RNG: check_unseeded_rng,
    rules.LINT_CSR_ENTRY: check_csr_entry,
    rules.LINT_BARE_EXCEPT: check_bare_except,
}


def run_selftests(log=None) -> list[str]:
    """Run every mutation self-test; returns failure messages
    (empty = all detectors proved live)."""
    failures: list[str] = []
    for rule_id, check in SELFTESTS.items():
        try:
            check()
            if log:
                log(f"  selftest {rule_id}: detector fired")
        except SelfTestError as e:
            failures.append(f"{rule_id}: {e}")
            if log:
                log(f"  selftest {rule_id}: FAILED ({e})")
    return failures
