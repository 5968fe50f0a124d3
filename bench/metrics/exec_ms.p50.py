"""Median wall time of a flush's model execution, logits on the host
(host clock around ``run_partitioned`` inside the flush hook)."""
import numpy as np


def read(run):
    x = [f.exec_ms for f in run.flushes if f.sizes]
    return float(np.median(x)) if x else None
