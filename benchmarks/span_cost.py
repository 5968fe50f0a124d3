#!/usr/bin/env python
"""Cost of one program span site (``repro.core.telemetry.span``).

Times ``with span(...): pass`` with no profiler running, then under one
with the Python tracer off (as ``bench/run.py`` traces), writing its
capture to a temporary directory that is removed afterwards.

  PYTHONPATH=src python benchmarks/span_cost.py [--n 200000]

Prints one JSON line: nanoseconds per site, ``off`` and ``on``, and the
device JAX found.
"""
import argparse
import json
import shutil
import sys
import tempfile
import time

import jax

from repro.core.telemetry import span


def ns_per_span(n: int) -> float:
    for _ in range(1000):
        with span("repro.og.fold"):
            pass
    t0 = time.perf_counter_ns()
    for _ in range(n):
        with span("repro.og.level", level=3):
            pass
    return (time.perf_counter_ns() - t0) / n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=200_000)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    off = ns_per_span(args.n)
    logdir = tempfile.mkdtemp(prefix="span_cost_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        on = ns_per_span(args.n // 10)
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(logdir, ignore_errors=True)
    print(json.dumps(dict(off_ns=off, on_ns=on, n=args.n,
                          device=dict(platform=dev.platform,
                                      kind=dev.device_kind))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
