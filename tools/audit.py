"""Determinism & VMEM invariant auditor CLI (DESIGN.md S14).

Runs the three static-analysis layers over the live tree and exits
nonzero on any finding:

* ``jaxpr``  — abstract-trace the registry workload x solver route
  matrix through the real epoch builders and walk the jaxprs for
  determinism-contract bugs (psum exchanges, shard_map loop-closure
  hazards, unordered reductions);
* ``lint``   — stdlib AST rules (kernel contract registry, collective
  allowlist markers, unseeded RNG, CSR entry altitudes);
* ``budget`` — sweep a grid of plans `core.planner.resolve_plan`
  resolves against the kernels' own VMEM estimators.

``--selftest`` additionally runs the mutation self-tests (one injected
bug per rule ID; each must be detected).  Usage::

    PYTHONPATH=src python tools/audit.py [--report AUDIT.json]
        [--selftest] [--layers jaxpr,lint,budget] [--workloads a,b]

No accelerator needed: traces run on forced host devices with Pallas
interpret mode (XLA_FLAGS is set below, BEFORE jax loads).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

# must precede any (transitive) jax import: the audit matrix needs 8
# host devices for its 1x2x2 meshes
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))


def main(argv: list[str] | None = None) -> int:
    """CLI entry point; returns a process exit status."""
    from repro.analysis import runner

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--report", metavar="PATH",
                    help="write the JSON report here")
    ap.add_argument("--selftest", action="store_true",
                    help="also run the mutation self-tests")
    ap.add_argument("--layers", default=",".join(runner.LAYERS),
                    help="comma-separated subset of "
                         f"{','.join(runner.LAYERS)}")
    ap.add_argument("--workloads", default="",
                    help="comma-separated registry names (default: all)")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress per-case progress lines")
    args = ap.parse_args(argv)

    log = (lambda s: None) if args.quiet else print
    layers = [s for s in args.layers.split(",") if s]
    workloads = [s for s in args.workloads.split(",") if s] or None

    report = runner.run_audit(layers=layers, workloads=workloads,
                              log=log)
    for f in report.findings:
        print(f"FINDING: {f}", file=sys.stderr)

    failures: list[str] = []
    if args.selftest:
        from repro.analysis import selftest
        log("[selftest] mutation checks, one per rule ID")
        failures = selftest.run_selftests(log=log)
        for msg in failures:
            print(f"SELFTEST FAILURE: {msg}", file=sys.stderr)

    if args.report:
        doc = report.to_json()
        if args.selftest:
            doc["selftest_failures"] = failures
        Path(args.report).write_text(json.dumps(doc, indent=2) + "\n")
        log(f"report written to {args.report}")

    print(f"audit: {len(report.cases)} traced case(s), "
          f"{report.plans_swept} plan(s) swept, "
          f"{len(report.findings)} finding(s)"
          + (f", {len(failures)} selftest failure(s)"
             if args.selftest else ""))
    return 1 if (report.findings or failures) else 0


if __name__ == "__main__":
    raise SystemExit(main())
