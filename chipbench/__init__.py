"""Chip benchmark of the GLM solver: time to a duality gap (see run.py)."""
