"""The program's own wall-clock spans (``repro.*``) in a profiler trace.

The program opens them at its layer boundaries (the event loop's drain,
flush and booking, the planner's dispatch, fetch and reconstruction, the
grouping DP's levels, the executor's steps; the program's
``telemetry.WALL_SPANS`` lists them).  Each is a host event of the trace,
on the device ops' clock, with its arguments as event stats.  This
module reduces a trace as ``bench/trace.py`` does, with the program's
spans beside the harness's ``bench.*`` ones:

* :func:`flush_totals`: seconds, bytes and count by program span, per
  harness flush span of the window (``bench.drain``, or ``bench.plan``
  for a wave);
* :func:`splits`: the per-flush quantities of :data:`SPLITS` and
  ``to_host_gbps`` from those;
* :func:`idle`: each device-idle gap given to the innermost span of
  either kind, and the share of the idle time inside :data:`HARNESS`
  spans that lies inside a program span other than :data:`OUTER`.

Run as

    python3 -m bench.spans --workload <cell> --seed <n> --seconds <s>

it drives one cell traced, as ``bench.run --trace 1`` does, and prints all
of that with the cell's per-layer metrics as one JSON line.  On a trace
without program spans every split reads ``None``.  ``bench/trace.py`` and
the per-layer metrics read only the harness's spans.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import NamedTuple

import numpy as np

from bench import trace as tr

#: the harness's flush spans: what one flush of the window is
FLUSH = ("bench.drain", "bench.plan")
#: the harness spans whose idle time the program's spans should name
HARNESS = ("bench.drain", "bench.exec", "bench.plan")
#: program spans that only enclose others
OUTER = ("repro.loop.flush", "repro.og.plan", "repro.og.level")
#: the executor's host work: everything but the wait and the copy
EXEC_HOST = ("repro.exec.prepare", "repro.exec.split", "repro.exec.blocks",
             "repro.exec.head", "repro.exec.scatter")
#: per-flush medians, in ms: name -> (program spans summed, the span
#: family a flush must have run to count)
SPLITS = {
    "exec_wait_ms.p50": (("repro.exec.wait",), "repro.exec."),
    "exec_to_host_ms.p50": (("repro.exec.to_host",), "repro.exec."),
    "exec_host_ms.p50": (EXEC_HOST, "repro.exec."),
    "plan_wait_ms.p50": (("repro.plan.fetch",), "repro.plan."),
    "plan_host_ms.p50": (("repro.plan.dispatch", "repro.plan.reconstruct"),
                         "repro.plan."),
    "og_host_ms.p50": (("repro.og.segments", "repro.og.fold"), "repro.og."),
    "loop_drain_ms.p50": (("repro.loop.drain",), "repro.loop."),
}


class Span(NamedTuple):
    name: str
    start: float        # s
    end: float          # s
    thread: tuple       # (plane, line): spans of one thread nest
    args: dict


def load(logdir: str):
    """The newest trace under ``logdir``: the device's busy intervals and
    the host spans named ``bench.*`` or ``repro.*``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    busy, spans = [], []
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            ops = [lines["XLA Ops"]] if "XLA Ops" in lines else \
                [line for name, line in lines.items() if name not in tr._SKIP]
            busy += [(e.start, e.end) for line in ops
                     for e in tr._events(line)]
        elif plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                spans += [Span(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                               (plane.name, k), dict(ev.stats))
                          for ev in line.events
                          if ev.name.startswith(("bench.", "repro."))]
    return busy, spans


def window(spans) -> tuple[float, float]:
    win = [s for s in spans if s.name == tr.WINDOW]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    return win[0].start, win[0].end


def flush_totals(spans) -> list[dict]:
    """For each harness flush span inside the window, oldest first,
    ``{program span: [seconds, bytes, count]}`` summed over the program
    spans it holds."""
    lo, hi = window(spans)
    flushes = sorted((s for s in spans if s.name in FLUSH
                      and lo <= s.start and s.end <= hi),
                     key=lambda s: s.start)
    prog = sorted((s for s in spans if s.name.startswith("repro.")),
                  key=lambda s: s.start)
    starts = [s.start for s in prog]
    out = []
    for f in flushes:
        t: dict = {}
        for s in prog[bisect.bisect_left(starts, f.start):
                      bisect.bisect_right(starts, f.end)]:
            if s.end <= f.end:
                x = t.setdefault(s.name, [0.0, 0, 0])
                x[0] += s.end - s.start
                x[1] += int(s.args.get("bytes", 0))
                x[2] += 1
        out.append(t)
    return out


def splits(flushes) -> dict:
    """:data:`SPLITS` over ``flushes`` (from :func:`flush_totals`): each
    the median over the flushes that ran a span of its family, ``None``
    where none did; and ``to_host_gbps``, the logits' bytes over the
    seconds of their copies to the host."""
    out = {}
    for name, (parts, family) in SPLITS.items():
        x = [1e3 * sum(f[p][0] for p in parts if p in f) for f in flushes
             if any(k.startswith(family) for k in f)]
        out[name] = float(np.median(x)) if x else None
    copies = [f["repro.exec.to_host"] for f in flushes
              if "repro.exec.to_host" in f]
    s = sum(c[0] for c in copies)
    out["to_host_gbps"] = sum(c[1] for c in copies) / s / 1e9 if s > 0 \
        else None
    return out


def holders(spans, times) -> list:
    """For each of the ascending ``times``, the span holding it that
    started last, or ``None``.  Spans of one thread nest, so a stack per
    thread finds it in one sweep."""
    spans = sorted(spans, key=lambda s: (s.start, -s.end))
    stacks: dict = {}
    out, k = [], 0
    for t in times:
        while k < len(spans) and spans[k].start <= t:
            st = stacks.setdefault(spans[k].thread, [])
            while st and st[-1].end < spans[k].start:
                st.pop()
            st.append(spans[k])
            k += 1
        best = None
        for st in stacks.values():
            while st and st[-1].end < t:
                st.pop()
            if st and (best is None or st[-1].start >= best.start):
                best = st[-1]
        out.append(best)
    return out


def intersect(a, b) -> list:
    """The intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle(busy, spans) -> dict:
    """The window's device-idle time: the longest gaps, each named by the
    innermost span of either kind holding its middle (as
    ``bench/trace.py`` names them); the idle seconds by the innermost span
    holding each moment of them; and the share of the idle time inside
    :data:`HARNESS` spans that lies inside a program span other than
    :data:`OUTER`."""
    lo, hi = window(spans)
    bench = [s for s in spans if s.name.startswith("bench.")
             and s.name != tr.WINDOW]
    prog = [s for s in spans if s.name.startswith("repro.")]
    gaps = tr.idle_gaps(busy, lo, hi)
    mids = [0.5 * (s + e) for s, e in gaps]
    rows = []
    for (s, e), h, p in zip(gaps, holders(bench, mids), holders(prog, mids)):
        # a program span opens inside the harness span that holds it
        inner = p if p is not None and (h is None or p.start >= h.start) \
            else h
        rows.append((e - s, "host.other" if inner is None else inner.name))
    rows.sort(key=lambda r: -r[0])
    # the idle time itself split at every span edge: each piece goes to
    # the innermost span holding it
    edges = sorted({t for sp in bench + prog for t in (sp.start, sp.end)
                    if lo < t < hi} | {lo, hi})
    pieces = intersect(gaps, list(zip(edges, edges[1:])))
    mids = [0.5 * (s + e) for s, e in pieces]
    by_name: dict = {}
    for (s, e), h, p in zip(pieces, holders(bench, mids),
                            holders(prog, mids)):
        inner = p if p is not None and (h is None or p.start >= h.start) \
            else h
        name = "host.other" if inner is None else inner.name
        by_name[name] = by_name.get(name, 0.0) + (e - s)
    held = intersect(gaps, tr.merged((s.start, s.end) for s in bench
                                     if s.name in HARNESS))
    named = intersect(held, tr.merged((s.start, s.end) for s in prog
                                      if s.name not in OUTER))
    held_s = tr.union_length(held)
    return dict(window_s=hi - lo, idle_s=sum(d for d, _ in rows),
                idle_in_harness_s=held_s,
                named_share=tr.union_length(named) / held_s if held_s > 0
                else None,
                longest=[[name, d] for d, name in rows[:10]],
                idle_by_name=dict(sorted(by_name.items(),
                                         key=lambda kv: -kv[1])))


def main(argv=None) -> int:
    import argparse
    import json
    import sys
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import run
    sys.path.insert(0, str(run.ROOT / "src"))
    # bench.run reads the trace and then deletes it: read it here too
    seen = {}
    plain = tr.read

    def read_too(logdir):
        seen["busy"], seen["spans"] = load(logdir)
        return plain(logdir)

    tr.read = read_too
    spec = run.load_spec()
    try:
        x = run.execute(spec, args.workload, args.seed, args.seconds, True)
    except run.NoChip as e:
        print(f"bench.spans: {e}; nothing run", file=sys.stderr)
        return 2
    finally:
        tr.read = plain
    out = run.result(spec, x, args.seed, True)
    flushes = flush_totals(seen["spans"])
    ms = {k: [f.ms for f in x["flushes"] if f.traced == k]
          for k in (True, False)}

    def p50(v):
        return float(np.median(v)) if v else None

    print(json.dumps(dict(
        workload=args.workload, seed=args.seed, correct=out["correct"],
        device=out["device"],
        metrics={k: m["value"] for k, m in out["metrics"].items()},
        splits=splits(flushes), idle=idle(seen["busy"], seen["spans"]),
        flush_ms_p50=dict(traced=p50(ms[True]), untraced=p50(ms[False])),
        flushes=dict(traced=len(ms[True]), untraced=len(ms[False]),
                     in_trace=len(flushes)),
        spans_per_flush=p50([sum(c[2] for c in f.values())
                             for f in flushes]))))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
