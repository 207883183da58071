#!/usr/bin/env python3
"""The control: the plain reference solver in the program's place.

For each seed this draws a cell's data, solves it with
`reference.solve_reference` in each dtype asked for, and checks the
answers exactly as a run checks the program's.  In bfloat16, the step
below the configuration's float32, the answers must fail; in float32
the same solver is the witness that the failure is the precision's.

    python3 chipbench/control.py --workload criteo-1chip --seeds 1,2,3

The benchmark's own runs do not run it.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time


def safe_batch(lam: float, n: int) -> int:
    """The largest power of two not above lam*n: a mini-batch that size
    scales each coordinate's curvature term by at most about 2."""
    return 1 << max(0, int(math.floor(math.log2(lam * n))))


def control_readings(cell: dict, seed: int, dtype: str,
                     n: int | None = None) -> dict:
    """The checked numbers of the reference's answer in `dtype`."""
    import jax.numpy as jnp
    from chipbench import gen, reference
    cfgd, c = cell["config"], cell["cell"]
    n = n or cfgd["n"]
    data = gen.make_data(cfgd, n, seed)
    target = float(c["target_gap"])
    t0 = time.perf_counter()
    v, alpha, epochs, claimed = reference.solve_reference(
        data, cfgd["lam"], cfgd["d"], dtype=getattr(jnp, dtype),
        batch=safe_batch(cfgd["lam"], n), target_gap=target,
        max_epochs=int(c["control"]["max_epochs"]), seed=seed)
    solve_s = time.perf_counter() - t0
    prob = reference.Problem(data, cfgd["lam"], cfgd["d"])
    checks = reference.check_solve(prob, v, alpha)
    limits = {k: float(c["limits"][k]) for k in reference.CHECKS}
    return {"dtype": dtype, "epochs": epochs, "claimed_gap": claimed,
            "solve_s": solve_s, "checks": checks,
            "correct": reference.within(checks, limits)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--dtypes", default="bfloat16,float32")
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from chipbench import run
    cell = run.load_cell(args.workload)
    run.find_devices(1)
    for seed in (int(s) for s in args.seeds.split(",")):
        for dtype in args.dtypes.split(","):
            out = control_readings(cell, seed, dtype)
            print(json.dumps(dict(out, workload=args.workload, seed=seed)),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
