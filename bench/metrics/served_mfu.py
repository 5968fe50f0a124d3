"""Model FLOPs of every request served in the traced window (all layers
and the head, counted from shapes by ``bench/flops.py`` and the model's
reference module) over the traced window's length times the chip's bf16
peak."""
from bench import flops


def read(run):
    if run.model is None or run.peak is None or run.trace is None:
        return None
    total = sum(flops.forward_flops(run.model, f.n, run.seq, run.root)
                for f in run.flushes if f.sizes and f.traced)
    return 100.0 * total / (run.trace.window_s * run.peak["bf16_flops"]) \
        if total else None
