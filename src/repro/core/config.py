"""Layered solver configuration: algorithm knobs x deployment knobs.

The paper's solver has two kinds of parameters that used to be tangled
in one flat `SolverConfig` (simulator) and duplicated in `GLMScale`
(distributed launcher):

  * `AlgoConfig` — properties of the *algorithm*: bucket size, sync
    interval, aggregation rule, partition scheme, wire compression.
    These determine convergence and are backend-independent.
  * `DeploymentConfig` — properties of *where it runs*: how many pods
    and lanes (virtual workers in the simulator, mesh axes on TPU),
    feature sharding, cross-pod compression, and whether collectives
    must be bit-deterministic.

`EngineConfig` composes the two and is what `core.engine` consumes on
every path (simulated and distributed).  The legacy flat
`core.cocoa.SolverConfig` converts via `.to_engine()` and keeps working
everywhere an `EngineConfig` is accepted.
"""
from __future__ import annotations

import dataclasses
from typing import Literal

Aggregation = Literal["wild", "adding", "averaging"]

#: local-solver implementations the engine can dispatch to, on BOTH the
#: dense and sparse paths.  "auto" resolves to "pallas" on TPU backends
#: and "xla" elsewhere ($REPRO_LOCAL_SOLVER overrides either way — see
#: engine.resolve_auto_solver).
LocalSolverKind = Literal["auto", "xla", "pallas"]


@dataclasses.dataclass(frozen=True)
class AlgoConfig:
    """Algorithm knobs (paper S3) — identical across backends."""
    bucket: int = 1                 # examples per bucket (1 = off)
    chunks: int = 1                 # v syncs per epoch (within pods)
    aggregation: Aggregation = "adding"
    partition: str = "hierarchical"  # static|dynamic|hierarchical|alltoall
    redeal_frac: float = 1.0        # alltoall: bucket fraction exchanged
    local_solver: LocalSolverKind = "auto"
    compress_sync: bool = False     # int8-quantize dv on the chunk sync
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class DeploymentConfig:
    """Where the solver runs: worker topology + wire/compute policies.

    The pods x lanes workers go on devices of their own where
    `lanes_on` says so: a resident `Session` then builds the
    ("pod","data","model") mesh itself and runs the shard_map epoch
    (launch/glm.py).  Otherwise they are virtual workers stacked on one
    device (the simulator)."""
    pods: int = 1                   # NUMA nodes -> TPU pods (static outer)
    lanes: int = 1                  # threads -> chips (dynamic inner)
    feature_shard: bool = False     # dense TP: shard d over 'model'
    compress_pod: bool = False      # int8 cross-pod epoch reduce
    # Bit-deterministic collectives: workers run unbatched (lax.map in
    # the simulator) and reductions are ordered gather-sums, so the sim
    # and mesh backends produce bitwise-identical results.  Costs some
    # throughput; off by default.
    deterministic: bool = False

    def lanes_on(self, devices: int) -> bool:
        """True where each of the pods x lanes workers (more than one)
        gets one of `devices` to itself, with features replicated: the
        one rule that puts lanes on chips."""
        return not self.feature_shard and 1 < self.pods * self.lanes <= devices


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """The one config both entry points consume (engine.run_epoch)."""
    algo: AlgoConfig = AlgoConfig()
    deployment: DeploymentConfig = DeploymentConfig()

    @classmethod
    def make(cls, **kw) -> "EngineConfig":
        """Build from flat kwargs, routing each to its layer."""
        af = {f.name for f in dataclasses.fields(AlgoConfig)}
        df = {f.name for f in dataclasses.fields(DeploymentConfig)}
        unknown = set(kw) - af - df
        if unknown:
            raise TypeError(f"unknown EngineConfig fields: {sorted(unknown)}")
        return cls(
            algo=AlgoConfig(**{k: v for k, v in kw.items() if k in af}),
            deployment=DeploymentConfig(
                **{k: v for k, v in kw.items() if k in df}))

    @property
    def workers(self) -> int:
        return self.deployment.pods * self.deployment.lanes

    def sigma_prime(self, workers: int | None = None) -> float:
        """CoCoA(+) subproblem scaling for `workers` independent solvers."""
        if self.algo.aggregation == "adding":
            return float(workers if workers is not None else self.workers)
        return 1.0


def as_engine_config(cfg) -> EngineConfig:
    """Accept an EngineConfig or anything exposing `.to_engine()`."""
    if isinstance(cfg, EngineConfig):
        return cfg
    to_engine = getattr(cfg, "to_engine", None)
    if to_engine is None:
        raise TypeError(f"cannot convert {type(cfg).__name__} to EngineConfig")
    return to_engine()
