#!/usr/bin/env python3
"""Faults planted under the timed path, to show that `correct` catches them.

Each fault breaks what a run's solves produce, and a run with it must
come out not correct (`chipbench/tests/test_faults.py`).  On the chip,
at a cell's own size, this script reads the checked numbers of one solve
under each fault:

    python3 chipbench/faults.py --workload criteo-1chip --seeds 1,2,3

* `frozen`: every epoch returns its state unchanged;
* `half_batch`: the dual updates of every other example are left out
  while v takes the whole update;
* `altered`: one entry of v is moved by 1e-3 where the epoch produces it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


class Fault:
    name = ""

    def wrap(self, session) -> None:
        """Break the built Session's epoch program."""

    @staticmethod
    def _around(session, fn) -> None:
        epoch = session._epoch_fn
        session._epoch_fn = lambda a, v, e: fn(a, v, *epoch(a, v, e))


class Frozen(Fault):
    name = "frozen"

    def wrap(self, session) -> None:
        self._around(session, lambda a, v, a1, v1: (a, v))


class HalfBatch(Fault):
    name = "half_batch"

    def wrap(self, session) -> None:
        import jax.numpy as jnp
        keep = jnp.arange(session.n) % 2 == 0
        self._around(session, lambda a, v, a1, v1: (jnp.where(keep, a1, a),
                                                     v1))


class Altered(Fault):
    name = "altered"

    def wrap(self, session) -> None:
        self._around(session, lambda a, v, a1, v1: (a1, v1.at[0].add(1e-3)))


FAULTS = {f.name: f for f in (Frozen(), HalfBatch(), Altered())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    sys.path.insert(0, os.path.join(root, "src"))
    from chipbench import run
    cell = run.load_cell(args.workload)
    faults = [FAULTS[f] for f in args.faults.split(",") if f] or \
        list(FAULTS.values())
    for seed in (int(s) for s in args.seeds.split(",")):
        for fault in [None] + faults:
            res = run.run_cell(cell, seed=seed, seconds=0.0, trace=False,
                               t_start=time.perf_counter(), fault=fault)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "fault": fault.name if fault else None,
                              "correct": res["correct"],
                              "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
