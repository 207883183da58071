"""Parallel linear-model training on TPU meshes (JAX/Pallas).

Reproduction of "Parallel training of linear models without
compromising convergence": bucketed CoCoA+/SDCA with dynamic partition
exchange, VMEM-resident Pallas bucket kernels, a versioned on-disk tile
cache, sklearn-compatible estimators, and one static rule that picks
the local solver's route from shape and platform (`core.planner`).
Start at `repro.api` (estimators + `Session`);
see README.md and DESIGN.md for the map.
"""
