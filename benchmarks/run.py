"""Run every paper-figure benchmark at reduced scale + the roofline table.

    PYTHONPATH=src python -m benchmarks.run [--full] [--json BENCH_2.json]

Each module prints its own CSV block; a machine-readable summary
(per-figure runtime, row count, final duality gap) is written as JSON
for CI artifacts / perf-trajectory tracking, and the process exits
non-zero when any figure module raises so a failing benchmark fails CI.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

from repro.compile_cache import enable_compile_cache

from . import (fig1_wild_convergence, fig2_scaling_partitions,
               fig3_convergence, fig4_strong_scaling, fig5_ablations,
               fig6_solvers, resilience, roofline)

# Bump when a figure's WORKLOAD changes (new arms, different sizes):
# benchmarks/compare.py only diffs runs with equal workload versions,
# so intentional changes reset the perf baseline instead of tripping
# the >20% regression gate.  v2: fig3/fig6 sklearn+estimator arms.
# v3: fig6 sparse xla-vs-pallas arms + deduped synthetic sparse rows.
# v4: fig6 feature-sharded sparse arm (webspam-shaped, model-axis mesh).
# v5: fig6 planner arm ($REPRO_PLAN=probe geometry search, chosen
#     SolverPlan emitted under figures[...]["plans"]).
# v6: resilience arm (journal + kill-and-resume recovery overhead,
#     emitted under figures[...]["recovery"]).
# v7: fig4 streamed-mesh arm (resident vs MeshChunkFeed-streamed epochs:
#     transfer-hidden fraction, ingest bytes measured + modeled) and
#     roofline t_h2d_s column.
WORKLOAD_VERSION = 7

BENCHES = [
    ("fig1_wild_convergence", fig1_wild_convergence),
    ("fig2_scaling_partitions", fig2_scaling_partitions),
    ("fig3_convergence", fig3_convergence),
    ("fig4_strong_scaling", fig4_strong_scaling),
    ("fig5_ablations", fig5_ablations),
    ("fig6_solvers", fig6_solvers),
    ("resilience", resilience),
    ("roofline", roofline),
]


def _final_gap(rows) -> float | None:
    gaps = [r["gap"] for r in rows
            if isinstance(r.get("gap"), float)]
    return gaps[-1] if gaps else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-shaped sizes (slower)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default="BENCH_2.json",
                    help="summary output path ('' disables)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    total = 0
    figures: dict[str, dict] = {}
    failed: list[str] = []
    for name, mod in BENCHES:
        if args.only and args.only not in name:
            continue
        print(f"\n===== {name} =====")
        t0 = time.perf_counter()
        try:
            rows = mod.run(quick=not args.full)
        except Exception:
            traceback.print_exc()
            failed.append(name)
            figures[name] = {"failed": True,
                             "runtime_s": time.perf_counter() - t0}
            print(f"----- {name}: FAILED")
            continue
        dt = time.perf_counter() - t0
        total += len(rows)
        figures[name] = {"failed": False, "runtime_s": dt,
                         "rows": len(rows), "final_gap": _final_gap(rows)}
        # sklearn-parity metrics from the fig3/fig6 estimator arms go
        # into the artifact so CI can track drift across runs
        parity = [{k: r.get(k) for k in ("dataset", "impl", "solver",
                                         "score", "score_sklearn",
                                         "predict_agree")
                   if r.get(k) is not None}
                  for r in rows if r.get("predict_agree") is not None]
        if parity:
            figures[name]["parity"] = parity
        # per-solver throughput from the fig6 sparse xla/pallas arms
        # rides along too, so CI can watch examples/s + HBM bytes drift
        thr = [{k: r.get(k) for k in ("dataset", "solver",
                                      "examples_per_s", "hbm_bytes_epoch",
                                      "transfer_hidden_frac",
                                      "h2d_bytes_epoch", "h2d_bytes_model")
                if r.get(k) is not None}
               for r in rows if r.get("examples_per_s") is not None]
        if thr:
            figures[name]["throughput"] = thr
        # chosen SolverPlans from planner arms (fig6) land next to the
        # throughput records: CI tracks WHAT the planner picked (bucket,
        # chunks, route, probe seconds), not just how fast it ran
        plans = [{"dataset": r.get("dataset"), "solver": r.get("solver"),
                  "examples_per_s": r.get("examples_per_s"),
                  "plan": r["plan"]}
                 for r in rows if r.get("plan") is not None]
        if plans:
            figures[name]["plans"] = plans
        # recovery-overhead ratios from the resilience arm: CI watches
        # the fault-free hot loop stay free and resume stay ~one-epoch
        recovery = [{k: r.get(k) for k in ("variant", "wall_s",
                                           "overhead_vs_clean")}
                    for r in rows if r.get("overhead_vs_clean")
                    is not None]
        if recovery:
            figures[name]["recovery"] = recovery
        print(f"----- {name}: {len(rows)} rows in {dt:.1f}s")

    print(f"\nbenchmarks complete: {total} rows"
          + (f", {len(failed)} FAILED: {failed}" if failed else ""))
    if args.json:
        summary = {"schema": "bench-summary/v1",
                   "workload": WORKLOAD_VERSION,
                   "quick": not args.full,
                   "figures": figures, "total_rows": total,
                   "failed": failed}
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"summary JSON: {args.json}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
