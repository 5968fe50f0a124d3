#!/usr/bin/env python
"""Host cost of landing a flush's logits: fresh memory against a pooled
buffer (``repro.serving.outputs.HostOutputs``).

For each batch size, a resident ``(B, S, V)`` float32 source (what the
copy off the device hands over) is scattered row by row into the output,
as ``run_partitioned`` does: into a fresh ``np.zeros`` per call (each
write faults its page in), into a new pool's first buffer per call (the
pool's path when every earlier output is still held), and into a buffer
the pool hands out again (its pages written by an earlier call).
Defaults are ``minitron4b.busy``'s: 32-token prompts, a 256,000-token
vocabulary, B = 4, 8, 12, 16.

  PYTHONPATH=src python benchmarks/host_fill.py [--sizes 4 8 12 16] [--n 5]

Prints one JSON line: per batch size the median ms of each way
(``fresh``, ``first``, ``pooled``) and the GB/s it lands the logits at.
Nothing touches an accelerator.
"""
import argparse
import json
import statistics
import sys
import time

import numpy as np

from repro.serving.outputs import HostOutputs


def fill_ms(take, src: np.ndarray, n: int) -> float:
    """Median ms of ``take()`` plus the scatter of ``src`` into it."""
    rows = np.arange(len(src))
    times = []
    for _ in range(n + 1):                    # the first call warms the pool
        t0 = time.perf_counter()
        out = take()
        out[rows] = src
        times.append((time.perf_counter() - t0) * 1e3)
        del out
    return statistics.median(times[1:])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sizes", type=int, nargs="+", default=[4, 8, 12, 16])
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--vocab", type=int, default=256_000)
    ap.add_argument("--n", type=int, default=5)
    args = ap.parse_args(argv)
    pool = HostOutputs()
    rows = {}
    for B in args.sizes:
        shape = (B, args.seq, args.vocab)
        src = np.ones(shape, np.float32)
        ms = dict(fresh=fill_ms(lambda: np.zeros(shape, np.float32), src,
                                args.n),
                  first=fill_ms(lambda: HostOutputs().take(shape)[0], src,
                                args.n),
                  pooled=fill_ms(lambda: pool.take(shape)[0], src, args.n))
        rows[B] = dict(mb=src.nbytes / 1e6,
                       **{f"{k}_ms": v for k, v in ms.items()},
                       **{f"{k}_gbps": src.nbytes / v / 1e6
                          for k, v in ms.items()})
        del src
    print(json.dumps(dict(sizes=rows, n=args.n, taken=pool.taken,
                          reused=pool.reused)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
