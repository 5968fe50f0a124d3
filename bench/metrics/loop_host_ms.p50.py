"""Median host time of the event loop per flush: the ``step_batch`` wall
time less the planner's and the model's time inside it."""
import numpy as np


def read(run):
    x = [f.ms - f.plan_ms - f.exec_ms for f in run.flushes
         if f.plan_ms is not None]
    return float(np.median(x)) if x else None
