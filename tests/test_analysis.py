"""Static-analysis auditor tests (DESIGN.md S14, docs/analysis.md).

The jaxpr-layer tests shell out with forced host devices (repo
convention: only launch entrypoints force device counts); the lint,
budget, and registry tests run in-process — they are stdlib-side.
"""
import dataclasses
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


def _run(code: str, timeout=600):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=str(REPO / "src"))
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)


# ---------------------------------------------------------------------------
# jaxpr layer: the loop-closure regression pair (the PR 1 / PR 6 bug
# class, reconstructed minimally) + a clean slice of the real matrix
# ---------------------------------------------------------------------------


def test_loop_closure_regression_pair():
    """The shard_map loop-invariant-replicated closure bug: a fori_loop
    body closing over an axis_index-derived offset MUST be flagged, and
    the xs-threaded form of the same program MUST pass.  This is the
    auditor-level pin of the bug `engine.run_epoch` unrolls its chunk
    loop to avoid and `ops.sdca_sparse_sharded_subepoch` passes `lo`
    as scanned xs to avoid."""
    r = _run("""
        import jax, jax.numpy as jnp
        from repro.core.engine import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.analysis import jaxpr_audit, rules
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(pod=1, data=2, model=1)

        def trace(inner):
            f = shard_map(inner, mesh, P("data"), P("data"))
            return jax.make_jaxpr(f)(jnp.zeros(8))

        def buggy(x):
            lo = jax.lax.axis_index("data") * 4
            def body(i, acc):
                return acc + x[lo + i]      # closed over -> replicated
            return jax.lax.fori_loop(0, 4, body, 0.0)[None]

        def threaded(x):
            lo = jax.lax.axis_index("data") * 4
            def body(acc, lo_i):            # a per-iteration input
                return acc + x[lo_i], None
            los = lo + jnp.arange(4)
            return jax.lax.scan(body, 0.0, los)[0][None]

        got = jaxpr_audit.audit_jaxpr(trace(buggy), deterministic=True)
        assert [f.rule for f in got] == [rules.JAX_LOOP_CLOSURE], got
        assert "carry" in got[0].message
        clean = jaxpr_audit.audit_jaxpr(trace(threaded),
                                        deterministic=True)
        assert clean == [], [str(f) for f in clean]
        print("OK")
        """)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


def test_matrix_clean_on_one_workload():
    """One registry workload through every route: the real epoch
    programs trace and audit clean (the full matrix is the CI audit
    job; this pins the plumbing inside tier-1)."""
    r = _run("""
        from repro.analysis import matrix
        found = matrix.run_matrix(["synthetic-sparse"])
        assert found == [], [str(f) for f in found]
        cases = [c.name for c in matrix.build_cases(["synthetic-sparse"])]
        assert "synthetic-sparse/pallas-sharded/det" in cases, cases
        print("OK", len(cases))
        """)
    assert r.returncode == 0, r.stderr
    assert "OK 6" in r.stdout


def test_psum_and_nondet_detectors_fire():
    """Injected psum / pmax inside shard_map are flagged under the
    deterministic contract and ignored outside it."""
    r = _run("""
        import jax, jax.numpy as jnp
        from repro.core.engine import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.analysis import jaxpr_audit, rules
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(pod=1, data=2, model=1)
        for fn, rule in [(jax.lax.psum, rules.JAX_PSUM_EXCHANGE),
                         (jax.lax.pmax, rules.JAX_NONDET_PRIM)]:
            f = shard_map(lambda x, fn=fn: fn(x, "data"), mesh,
                          P("data"), P(None))
            j = jax.make_jaxpr(f)(jnp.zeros(8))
            det = jaxpr_audit.audit_jaxpr(j, deterministic=True)
            assert [x.rule for x in det] == [rule], (rule, det)
            assert det[0].where, "findings must carry file:line anchors"
            nondet = jaxpr_audit.audit_jaxpr(j, deterministic=False)
            assert nondet == []
        print("OK")
        """)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


def test_selftests_all_pass():
    """Every mutation self-test proves its detector fires (the same
    gate the CI static-analysis job runs via --selftest)."""
    r = _run("""
        from repro.analysis import selftest
        failures = selftest.run_selftests()
        assert failures == [], failures
        assert len(selftest.SELFTESTS) == 9
        print("OK")
        """, timeout=900)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


# ---------------------------------------------------------------------------
# lint layer (in-process: stdlib AST, no jax)
# ---------------------------------------------------------------------------


def _analysis():
    sys.path.insert(0, str(REPO / "src"))
    from repro.analysis import config, lint, rules
    return config, lint, rules


def test_lint_clean_on_live_tree():
    config, lint, rules = _analysis()
    found = lint.run_lint()
    assert found == [], [str(f) for f in found]


def test_lint_flags_unmarked_collective_outside_scoped_files():
    """A collective appearing in a scoped file without a marker is
    flagged; the same source under a non-scoped path is not linted by
    the marker rule (the scope IS the rule)."""
    config, lint, rules = _analysis()
    src = "import jax\n\ndef f(x, ax):\n    return jax.lax.psum(x, ax)\n"
    scoped = config.COLLECTIVE_SCOPED_FILES[0]
    got = lint.run_lint({scoped: src},
                        only=[rules.LINT_RAW_COLLECTIVE])
    assert [f.rule for f in got] == [rules.LINT_RAW_COLLECTIVE]
    assert got[0].where == f"{scoped}:4"
    not_scoped = lint.run_lint({"src/repro/core/elsewhere.py": src},
                               only=[rules.LINT_RAW_COLLECTIVE])
    assert not_scoped == []


def test_lint_kernel_contract_and_rng_rules():
    config, lint, rules = _analysis()
    rogue = ("from jax.experimental import pallas as pl\n"
             "def rogue(x):\n"
             "    return pl.pallas_call(None, out_shape=x)(x)\n")
    got = lint.check_kernel_contracts(
        "src/repro/kernels/sdca_bucket.py", rogue, {})
    assert [f.rule for f in got] == [rules.LINT_KERNEL_CONTRACT]

    rng = "import numpy as np\nx = np.random.rand(3)\n"
    got = lint.check_unseeded_rng("src/repro/core/x.py", rng)
    assert [f.rule for f in got] == [rules.LINT_UNSEEDED_RNG]
    seeded = "import numpy as np\nr = np.random.default_rng(0)\n"
    assert lint.check_unseeded_rng("src/repro/core/x.py", seeded) == []


def test_quarantine_matches_ruff_exclude():
    """repro.analysis.config.QUARANTINE and pyproject.toml's ruff
    extend-exclude are the same list (README documents them as one
    policy; this is the pin)."""
    config, _, _ = _analysis()
    text = (REPO / "pyproject.toml").read_text()
    block = text.split("extend-exclude = [", 1)[1].split("]", 1)[0]
    excluded = {s.strip().strip('",') for s in block.splitlines()
                if s.strip().startswith('"')}
    assert excluded == set(config.QUARANTINE)


def test_rules_registry_complete():
    """Every rule ID has registry metadata (invariant + history) and
    every detector layer's IDs are registered."""
    _, _, rules = _analysis()
    assert set(rules.RULES) == {
        "JAX-PSUM-EXCHANGE", "JAX-LOOP-CLOSURE", "JAX-NONDET-PRIM",
        "LINT-KERNEL-CONTRACT", "LINT-RAW-COLLECTIVE",
        "LINT-UNSEEDED-RNG", "LINT-CSR-ENTRY", "LINT-BARE-EXCEPT",
        "VMEM-PLAN-BUDGET"}
    for rule in rules.RULES.values():
        assert rule.invariant and rule.history
        assert rule.layer in ("jaxpr", "lint", "budget")


# ---------------------------------------------------------------------------
# budget layer + misfit reason codes
# ---------------------------------------------------------------------------


def test_budget_audit_clean_and_catches_forged_plan():
    sys.path.insert(0, str(REPO / "src"))
    from repro.analysis import budget, rules
    from repro.core.planner import SolverPlan
    found, n_plans = budget.run_budget_audit()
    assert found == [], [str(f) for f in found]
    assert n_plans > 500          # the sweep actually swept

    forged = SolverPlan(solver="pallas", route="pallas-replicated",
                        bucket=512, chunks=1, nnz_multiple=0,
                        feature_shard=False)
    got = budget.audit_plan(forged, sparse=True, n=4096, d=64, nnz=2048)
    assert got and all(f.rule == rules.VMEM_PLAN_BUDGET for f in got)


def test_misfit_reasons_carry_stable_codes():
    """`ops` misfit reasons are str-compatible AND carry MisfitCode;
    the planner surfaces the code on SolverPlan.reason_code."""
    sys.path.insert(0, str(REPO / "src"))
    from repro.kernels import ops
    from repro.core.planner import resolve_plan

    route, reason = ops.sparse_solver_plan(100, 8, 64, 16)
    assert route == "xla"
    assert isinstance(reason, str) and "does not divide" in reason
    assert reason.code == ops.MisfitCode.BUCKET_INDIVISIBLE

    _, reason = ops.sparse_solver_plan(16, 12, 64, 16)
    assert reason.code == ops.MisfitCode.ALIGNMENT
    _, reason = ops.sparse_solver_plan(16, 8, 3_000_000, 16)
    assert reason.code == ops.MisfitCode.VMEM_V
    _, reason = ops.sparse_solver_plan(512, 2048, 64, 512)
    assert reason.code == ops.MisfitCode.VMEM_TOTAL

    why = ops.dense_kernel_misfit(64, 1024, 1024)
    assert why.code == ops.MisfitCode.BUCKET_CAP
    assert ops.dense_kernel_misfit(64, 64, 16) is None

    # planner surface: infeasible geometry -> code on the plan;
    # feasible -> empty code, reason "fits"
    plan = resolve_plan(sparse=True, n=4096, d=64, nnz=2048, bucket=512,
                        backend="tpu")
    assert plan.route == "xla"
    assert plan.reason_code == ops.MisfitCode.VMEM_TOTAL
    assert type(plan.reason) is str       # JSON-plain on the record
    fits = resolve_plan(sparse=True, n=4096, d=64, nnz=8, bucket=16,
                        backend="tpu")
    assert fits.reason == "fits" and fits.reason_code == ""
    doc = json.loads(json.dumps(dataclasses.asdict(fits)))
    assert doc["reason_code"] == ""


# ---------------------------------------------------------------------------
# CLI + report schema
# ---------------------------------------------------------------------------


def test_audit_cli_lint_layer_and_report(tmp_path):
    """The CLI's jax-free layer end-to-end: exit 0 on the clean tree,
    JSON report with the self-describing schema."""
    report = tmp_path / "AUDIT.json"
    r = subprocess.run(
        [sys.executable, str(REPO / "tools" / "audit.py"),
         "--layers", "lint,budget", "--report", str(report), "--quiet"],
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr
    import json
    doc = json.loads(report.read_text())
    assert doc["ok"] is True and doc["findings"] == []
    assert doc["version"] == 1 and doc["plans_swept"] > 500
    assert set(doc["rules"]) == {
        "JAX-PSUM-EXCHANGE", "JAX-LOOP-CLOSURE", "JAX-NONDET-PRIM",
        "LINT-KERNEL-CONTRACT", "LINT-RAW-COLLECTIVE",
        "LINT-UNSEEDED-RNG", "LINT-CSR-ENTRY", "LINT-BARE-EXCEPT",
        "VMEM-PLAN-BUDGET"}


def test_audit_cli_rejects_unknown_layer():
    r = subprocess.run(
        [sys.executable, str(REPO / "tools" / "audit.py"),
         "--layers", "nope"],
        capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "unknown audit layers" in (r.stderr + r.stdout)


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q"]))
