"""Quickstart: train the paper's solver through the sklearn-style API.

    PYTHONPATH=src python examples/quickstart.py

Walks the public `repro.api` surface end to end: estimator fit/predict/
score (drop-in sklearn shape), the Session underneath for epoch-level
control + callbacks, and the wild-vs-domesticated contrast the paper
is about.
"""

import numpy as np

from repro.api import (EarlyStopping, GapLogger, LogisticRegression,
                       Session)
from repro.compile_cache import enable_compile_cache
from repro.core import EngineConfig
from repro.data import make_dense_classification


def main() -> None:
    enable_compile_cache()
    # 16k examples x 100 dense features (the paper's Fig-1 shape).
    # Estimators speak sklearn layout: X (n_samples, n_features).
    Xcol, y = make_dense_classification(n=16_384, d=100, seed=0)
    X = np.asarray(Xcol).T

    print("== sklearn-style estimator (sequential baseline) ==")
    clf = LogisticRegression(lam=1e-3, bucket=8, max_epochs=40, tol=1e-4)
    clf.fit(X, y)
    print(f"epochs={clf.n_iter_} gap={clf.fit_result_.final_gap:.2e} "
          f"train-acc={clf.score(X, y):.4f}")
    print(f"proba[0]={clf.predict_proba(X[:1])[0]}")

    print("\n== domesticated parallel (2 pods x 8 lanes, dynamic) ==")
    par = LogisticRegression(lam=1e-3, bucket=8, pods=2, lanes=8,
                             partition="hierarchical",
                             aggregation="adding", max_epochs=60,
                             tol=1e-4)
    par.fit(X, y)
    print(f"epochs={par.n_iter_} gap={par.fit_result_.final_gap:.2e} "
          f"train-acc={par.score(X, y):.4f}")

    print("\n== Session: epoch-level control + callbacks ==")
    cfg = EngineConfig.make(pods=2, lanes=8, bucket=8,
                            partition="hierarchical")
    s = Session((Xcol, y), objective="logistic", lam=1e-3, cfg=cfg)
    rec = s.epoch()                       # run exactly ONE epoch
    print(f"one epoch: rel_change={rec['rel_change']:.3e}")
    res = s.fit(until=60, tol=0.0, callbacks=[
        GapLogger(every=10),
        EarlyStopping(monitor="gap", threshold=1e-4),   # certificate stop
    ])
    print(f"stopped at epoch {res.epochs} with gap={res.final_gap:.2e}")

    print("\n== 'wild' parallel (16 lock-free lanes) ==")
    wild = LogisticRegression(lam=1e-3, bucket=8, lanes=16,
                              partition="dynamic", aggregation="wild",
                              max_epochs=40, tol=1e-4)
    wild.fit(X, y)
    print(f"epochs={wild.n_iter_} "
          f"converged={wild.fit_result_.converged} "
          f"gap={wild.fit_result_.final_gap:.2e}"
          "  <- the paper's Fig-1 pathology")


if __name__ == "__main__":
    main()
