"""Median planner time per flush: the sum of the steady-state samples
``PlannerStats`` took inside each flush (compiles kept apart; a flush in
which the stats decimated their samples is left out)."""
import numpy as np


def read(run):
    x = [f.plan_ms for f in run.flushes if f.plan_ms is not None]
    return float(np.median(x)) if x else None
