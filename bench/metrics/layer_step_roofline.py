"""Least time the chip could take for every layer-step call of the traced
window (per call the larger of FLOPs over peak and bytes over bandwidth,
from shapes) over the device time of the ``_layer_step`` programs in the
trace.
The bound that sets the least time is printed to standard error."""
import sys

from bench import flops


def read(run):
    if run.model is None or run.trace is None or run.peak is None:
        return None
    device_s = run.trace.module_time("_layer_step")
    if device_s <= 0:
        return None
    least, by = 0.0, {"compute": 0.0, "memory": 0.0}
    for b in flops.layer_calls(f.sizes for f in run.flushes if f.traced):
        call = flops.layers_roofline_s(run.model, b, run.seq, run.peak,
                                       run.root)
        least += call["compute"] + call["memory"]
        for bound, t in call.items():
            by[bound] += t
    print(f"layer_step_roofline: least {least!r} s over device "
          f"{device_s!r} s; bound by compute {by['compute']!r} s, "
          f"memory {by['memory']!r} s", file=sys.stderr)
    return 100.0 * least / device_s
