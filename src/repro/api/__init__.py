"""The public training API: estimators + Session (DESIGN.md S10).

One front door for every backend and data source:

    from repro import api
    clf = api.LogisticRegression(lanes=8, bucket=8).fit(X, y)
    s = api.Session("higgs", streamed=True); s.fit(until=20)

Everything older (`GLMTrainer`, `StreamedGLMTrainer`, `fit_dataset`,
`cocoa.epoch_sim*`) is a deprecation shim over these — see the
migration map in DESIGN.md S10 and `ReproDeprecationWarning`.
"""
from .callbacks import Callback, CheckpointHook, EarlyStopping, GapLogger
from .deprecation import ReproDeprecationWarning, warn_deprecated
from .estimators import (GLMEstimator, LinearSVC, LogisticRegression,
                         NotFittedError, Ridge, load)
from .session import Session, margins
# resilience surface (repro.resilience re-exported here so the fault-
# tolerant knobs live next to the estimators that take them)
from repro.resilience import HealthMonitor, HealthPolicy

__all__ = [
    "Callback", "CheckpointHook", "EarlyStopping", "GapLogger",
    "ReproDeprecationWarning", "warn_deprecated",
    "GLMEstimator", "LinearSVC", "LogisticRegression", "NotFittedError",
    "Ridge", "load",
    "Session", "margins",
    "HealthMonitor", "HealthPolicy",
]
