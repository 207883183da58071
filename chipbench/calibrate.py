#!/usr/bin/env python3
"""Per-epoch duality gaps of a cell's solve, over seeds: the record that
places a cell's target gap between two epochs' gaps.

    python3 chipbench/calibrate.py --workload criteo-1chip \\
        --seeds 1,2,3 --epochs 6

Runs on whatever backend JAX finds (on the CPU the route is the XLA
scan; the kernels are bitwise equal to it on the chip for criteo).
Prints one JSON line a seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--epochs", type=int, default=6)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    sys.path.insert(0, os.path.join(root, "src"))
    from chipbench import gen, run
    cell = run.load_cell(args.workload)
    devices = run.find_devices(cell["chips"], require_tpu=False)
    if devices[0].platform == "tpu":
        run._enable_compile_cache()
    target = float(cell["cell"]["target_gap"])
    for seed in (int(s) for s in args.seeds.split(",")):
        data = gen.make_data(cell["config"], cell["config"]["n"], seed)
        s = run.build_session(cell, data)
        t0 = time.perf_counter()
        res = s.fit(max_epochs=args.epochs, tol=0.0, gap_every=1)
        gaps = [h["gap"] for h in res.history]
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "backend": devices[0].platform,
            "epochs_to_target": next((i + 1 for i, g in enumerate(gaps)
                                      if g < target), None),
            "gaps": gaps, "seconds": time.perf_counter() - t0}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
