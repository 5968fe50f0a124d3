"""Compiles inside the window: planner executable-cache misses plus JAX
lowerings (every jit cache miss lowers before it compiles or loads)."""


def read(run):
    return float(run.compiles)
