"""Train on a registry dataset through the real-data pipeline.

    PYTHONPATH=src python examples/glm_dataset.py --dataset higgs
    PYTHONPATH=src python examples/glm_dataset.py \
        --dataset criteo-kaggle-sub --streamed

Walks the pipeline end to end: registry name -> (svmlight/CSV file if
one sits under --data-dir / $REPRO_DATA_DIR, else the seeded synthetic
stand-in) -> packed bucket-tile cache (built once, mmap'd after) ->
in-memory or out-of-core streamed training.  With --verify both modes
run and the script checks they agree bitwise (deterministic engine).
"""
import argparse
import tempfile

import numpy as np

from repro.api import Session
from repro.compile_cache import enable_compile_cache
from repro.core import EngineConfig
from repro.data import get_spec


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dataset", default="higgs",
                    help="registry name (higgs, epsilon, "
                         "criteo-kaggle-sub, webspam, synthetic-*)")
    ap.add_argument("--streamed", action="store_true",
                    help="train out of core through the tile cache")
    ap.add_argument("--verify", action="store_true",
                    help="run BOTH modes and check bitwise agreement")
    ap.add_argument("--cache-dir", default=None,
                    help="tile-cache directory (default: temp dir)")
    ap.add_argument("--data-dir", default=None,
                    help="directory with real <name>.svm/.csv files")
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--epochs", type=int, default=30)
    args = ap.parse_args()

    spec = get_spec(args.dataset)
    print(f"dataset {spec.name}: {spec.kind}, real shape "
          f"{spec.full_n} x {spec.full_d}, objective {spec.objective}")
    print(f"  source: {spec.source}")

    cfg = EngineConfig.make(pods=2, lanes=4, bucket=8, chunks=2,
                            partition="hierarchical",
                            deterministic=args.verify)
    cache_dir = args.cache_dir or tempfile.mkdtemp(prefix="repro-cache-")
    ses_kw = dict(cfg=cfg, n=args.n, cache_dir=cache_dir,
                  data_dir=args.data_dir)
    fit_kw = dict(max_epochs=args.epochs, tol=1e-4, gap_every=10,
                  verbose=True)

    modes = [args.streamed] if not args.verify else [False, True]
    results = {}
    for streamed in modes:
        label = "streamed" if streamed else "in-memory"
        print(f"\n== {label} training ==")
        res = Session(args.dataset, streamed=streamed, **ses_kw).fit(
            **fit_kw)
        print(f"{label}: epochs={res.epochs} converged={res.converged} "
              f"gap={res.final_gap:.3e} wall={res.wall_time:.2f}s")
        results[streamed] = res

    if args.verify:
        same = (np.array_equal(results[False].v, results[True].v)
                and np.array_equal(results[False].alpha,
                                   results[True].alpha))
        print(f"\nstreamed == in-memory bitwise: {same}")


if __name__ == "__main__":
    main()
