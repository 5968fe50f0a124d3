"""Reduction of a profiler trace to device busy time, operation and module
times, and idle gaps attributed to the harness's host spans.

Reads the ``.xplane.pb`` the JAX profiler writes with
``jax.profiler.ProfileData``.  Device planes are those named
``/device:<kind>:<n>``; their operations are the events of the
``XLA Ops`` line and their compiled programs those of ``XLA Modules``
(every line but the modules, steps and source lines where a plane has no
ops line).  Host spans are the events named ``bench.*`` on the host plane.
All times are in seconds, clipped to the window span ``bench.window``.
"""
from __future__ import annotations

import dataclasses
import glob
import os

WINDOW = "bench.window"
_SKIP = ("XLA Modules", "Steps", "Source code", "Framework Ops",
         "Framework Name Scope", "XLA TraceMe")


@dataclasses.dataclass
class Event:
    name: str
    start: float       # s
    end: float         # s


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                      # mean over device planes
    devices: int
    op_s: dict                         # op name -> summed device seconds
    module_s: dict                     # program name -> summed seconds
    gaps: list                         # [(host span, seconds)], longest first

    def module_time(self, part: str) -> float:
        return sum(v for k, v in self.module_s.items() if part in k)

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:n]]}


def merged(intervals) -> list:
    """The union of (start, end) intervals as disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_length(intervals) -> float:
    return sum(e - s for s, e in merged(intervals))


def idle_gaps(busy, lo: float, hi: float) -> list:
    """The (start, end) stretches of [lo, hi] that no busy interval covers."""
    gaps, t = [], lo
    for s, e in merged(busy):
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [(s, e) for s, e in gaps if e > s]


def attribute(gap, spans) -> str:
    """The innermost host span (latest start) holding the gap's middle."""
    mid = 0.5 * (gap[0] + gap[1])
    best = None
    for sp in spans:
        if sp.start <= mid <= sp.end and (best is None
                                          or sp.start >= best.start):
            best = sp
    return best.name if best is not None else "host.other"


def summarize(device_planes: dict, host_spans: list) -> Summary:
    """``device_planes``: {plane: {"ops": [Event], "modules": [Event]}};
    ``host_spans``: [Event] including the ``bench.window`` span."""
    win = [s for s in host_spans if s.name == WINDOW]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = win[0].start, win[0].end
    spans = [s for s in host_spans if s.name != WINDOW]
    busy_total, ops, mods, all_busy = 0.0, {}, {}, []
    for plane in device_planes.values():
        iv = []
        for ev in plane["ops"]:
            s, e = max(ev.start, lo), min(ev.end, hi)
            if e > s:
                iv.append((s, e))
                ops[ev.name] = ops.get(ev.name, 0.0) + (e - s)
        for ev in plane["modules"]:
            s, e = max(ev.start, lo), min(ev.end, hi)
            if e > s:
                mods[ev.name] = mods.get(ev.name, 0.0) + (e - s)
        busy_total += union_length(iv)
        all_busy.extend(iv)
    n = max(len(device_planes), 1)
    gaps = [(attribute(g, spans), g[1] - g[0])
            for g in idle_gaps(all_busy, lo, hi)]
    gaps.sort(key=lambda kv: -kv[1])
    return Summary(hi - lo, busy_total / n, len(device_planes), ops, mods,
                   gaps)


def _events(line) -> list:
    return [Event(ev.name, ev.start_ns * 1e-9,
                  (ev.start_ns + ev.duration_ns) * 1e-9)
            for ev in line.events]


def read(logdir: str) -> Summary:
    """Summarize the newest trace under ``logdir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(files[-1])
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines:
                ops = _events(lines["XLA Ops"])
            else:
                ops = [e for name, line in lines.items() if name not in _SKIP
                       for e in _events(line)]
            mods = _events(lines["XLA Modules"]) if "XLA Modules" in lines \
                else []
            if ops or mods:
                devices[plane.name] = {"ops": ops, "modules": mods}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(e for e in _events(line)
                             if e.name.startswith("bench."))
    return summarize(devices, spans)


def rows_to_events(rows) -> list:
    """[(name, start_s, end_s)] -> [Event] (tests and recorded traces)."""
    return [Event(n, float(s), float(e)) for n, s, e in rows]
