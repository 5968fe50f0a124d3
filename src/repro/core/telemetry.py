"""Unified telemetry: structured event tracing + a metrics registry.

Every subsystem in the stack (planner, event loop, GPU timeline, channel,
tenancy arbiter, serving) used to emit its own ad-hoc counters.  This
module is the single observability substrate they thread through:

* :class:`Tracer` — typed span/instant events on **simulation time**,
  exported as Chrome trace-event JSON (load ``--trace out.json`` at
  https://ui.perfetto.dev).  One track per tenant plus dedicated GPU,
  uplink and planner tracks.
* :class:`MetricsRegistry` — counters / gauges / histograms with
  p50/p95/p99 digests; the sink the scattered per-run counters flow
  through.
* :class:`Telemetry` — the bundle handed to schedulers, plus the
  per-request lifecycle log (arrival → flush → gpu_start → done, slack
  at completion, energy).
* :func:`span` — wall-clock program spans on the **profiler's clock**
  (``jax.profiler.TraceAnnotation``), at the layer boundaries of the
  main path; every name is in :data:`WALL_SPANS`.  Capture them with
  ``jax.profiler.trace(dir)`` around a run: they sit on the host thread
  beside the device ops (Perfetto or TensorBoard).

Determinism contract
--------------------
All event timestamps are **sim-time** (seconds, scaled to µs for the
Chrome format).  No wall-clock value ever enters an event payload, so a
fixed ``--arrival-seed`` run produces a byte-stable trace.  The one
wall-clock measurement in the stack — planner dispatch latency, recorded
with ``perf_counter_ns`` by ``PlannerStats`` — is exported under an
explicit ``wall_time`` section of the metrics document, never into the
trace.

Overhead contract
-----------------
The null tracer (:data:`NULL_TRACER`) is allocation-free: hot paths
guard emission with ``if tracer.enabled:`` so a disabled run performs
one attribute load per site and allocates nothing.  Results must be
bit-identical with tracing on vs off — emission sites are read-only
observers and never perturb float math or control flow
(tests/core/test_telemetry.py pins both properties).

Wall-clock spans
----------------
:func:`span` never enters the simulated :class:`Tracer` and never changes
a result or the control flow.  Under a profiler it is a
``jax.profiler.TraceAnnotation``; with none running, one shared no-op
context (a few hundred ns a site).  Sites sit at flush, plan-call and
DP-level granularity only, never per arrival, per user or per layer
step, so the off cost stays far below a flush.  There is no switch: the
profiler being on is the switch.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
from typing import Any, Iterable, Sequence

__all__ = [
    "NULL_TRACER", "NullTracer", "Tracer", "MetricsRegistry", "Telemetry",
    "PID_SIM", "TID_RUN", "TID_GPU", "TID_UPLINK", "TID_PLANNER",
    "tenant_tid", "validate_events", "validate_trace_file",
    "aggregate_counter_fields", "WALL_SPANS", "span",
]

# ---------------------------------------------------------------------------
# track layout: one Chrome "process" for the sim, one "thread" per track
# ---------------------------------------------------------------------------
PID_SIM = 1       # the simulated co-inference system
TID_RUN = 1       # whole-run span (B/E pair emitted by the launcher)
TID_GPU = 2       # reservation spans gpu_start→end with dispatched f_e
TID_UPLINK = 3    # upload spans, planned vs realized
TID_PLANNER = 4   # plan dispatch / speculation events
_TENANT_BASE = 10


def tenant_tid(tenant: int) -> int:
    """Track id for tenant ``tenant`` (requests, flushes, admission)."""
    return _TENANT_BASE + int(tenant)


class NullTracer:
    """Disabled tracer: every method is a no-op and ``enabled`` is False.

    Hot paths must guard with ``if tracer.enabled:`` so the disabled
    case costs one attribute load and zero allocations.
    """

    __slots__ = ()
    enabled = False

    def name_track(self, tid, name):
        pass

    def instant(self, name, t, tid, args=None):
        pass

    def span(self, name, t0, t1, tid, args=None):
        pass

    def begin(self, name, t, tid, args=None):
        pass

    def end(self, name, t, tid):
        pass

    def counter(self, name, t, values):
        pass


NULL_TRACER = NullTracer()


class Tracer:
    """Collects Chrome trace-event dicts on simulation time.

    ``t`` arguments are sim-time **seconds**; the Chrome format wants
    microseconds, so timestamps are scaled by 1e6 on emission.  Event
    order is emission order, which is deterministic for a deterministic
    run, and export is ``sort_keys`` JSON — together that makes traces
    byte-stable for a fixed arrival seed.
    """

    enabled = True

    def __init__(self) -> None:
        self.events: list[dict] = []
        self._named: dict[int, str] = {}
        self.events.append({
            "ph": "M", "ts": 0, "pid": PID_SIM, "tid": 0,
            "name": "process_name",
            "args": {"name": "co-inference sim (sim time)"},
        })

    # -- track naming -------------------------------------------------------
    def name_track(self, tid: int, name: str) -> None:
        """Attach a human-readable name to a track (idempotent)."""
        if tid not in self._named:
            self._named[tid] = name
            self.events.append({
                "ph": "M", "ts": 0, "pid": PID_SIM, "tid": tid,
                "name": "thread_name", "args": {"name": name},
            })

    # -- emission -----------------------------------------------------------
    def instant(self, name: str, t: float, tid: int,
                args: dict | None = None) -> None:
        ev = {"ph": "i", "ts": t * 1e6, "pid": PID_SIM, "tid": tid,
              "name": name, "s": "t"}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def span(self, name: str, t0: float, t1: float, tid: int,
             args: dict | None = None) -> None:
        """Complete ("X") span from sim time ``t0`` to ``t1``."""
        ev = {"ph": "X", "ts": t0 * 1e6, "dur": (t1 - t0) * 1e6,
              "pid": PID_SIM, "tid": tid, "name": name}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def begin(self, name: str, t: float, tid: int,
              args: dict | None = None) -> None:
        ev = {"ph": "B", "ts": t * 1e6, "pid": PID_SIM, "tid": tid,
              "name": name}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def end(self, name: str, t: float, tid: int) -> None:
        self.events.append({"ph": "E", "ts": t * 1e6, "pid": PID_SIM,
                            "tid": tid, "name": name})

    def counter(self, name: str, t: float, values: dict) -> None:
        self.events.append({"ph": "C", "ts": t * 1e6, "pid": PID_SIM,
                            "tid": 0, "name": name, "args": values})

    # -- export -------------------------------------------------------------
    def to_chrome(self) -> dict:
        return {"traceEvents": self.events, "displayTimeUnit": "ms"}

    def export(self, path: str) -> None:
        """Write Perfetto-loadable Chrome trace-event JSON (byte-stable)."""
        with open(path, "w") as fh:
            json.dump(self.to_chrome(), fh, sort_keys=True,
                      separators=(",", ":"))
            fh.write("\n")


# ---------------------------------------------------------------------------
# trace-schema validation (used by benchmarks/validate_trace.py, CI, tests)
# ---------------------------------------------------------------------------
_REQUIRED_KEYS = ("ph", "ts", "pid", "tid", "name")


def validate_events(events: Sequence[dict]) -> list[str]:
    """Check Chrome trace-event invariants; return a list of problems.

    Required keys ``ph/ts/pid/tid/name`` on every event, non-negative
    ``dur`` on complete ("X") spans, and monotone B/E nesting per
    (pid, tid) track — no span may end before it starts and every E
    must close the innermost open B.
    """
    problems: list[str] = []
    stacks: dict[tuple, list[tuple[str, float]]] = {}
    for k, ev in enumerate(events):
        missing = [key for key in _REQUIRED_KEYS if key not in ev]
        if missing:
            problems.append(f"event {k}: missing keys {missing}: {ev}")
            continue
        ph, ts = ev["ph"], ev["ts"]
        track = (ev["pid"], ev["tid"])
        if ph == "X":
            dur = ev.get("dur")
            if dur is None:
                problems.append(f"event {k}: X span without dur: {ev}")
            elif dur < 0:
                problems.append(
                    f"event {k}: span {ev['name']!r} ends before it "
                    f"starts (dur={dur})")
        elif ph == "B":
            stacks.setdefault(track, []).append((ev["name"], ts))
        elif ph == "E":
            stack = stacks.setdefault(track, [])
            if not stack:
                problems.append(
                    f"event {k}: E {ev['name']!r} with no open B on "
                    f"track {track}")
                continue
            b_name, b_ts = stack.pop()
            if b_name != ev["name"]:
                problems.append(
                    f"event {k}: E {ev['name']!r} closes B {b_name!r} "
                    f"on track {track}")
            if ts < b_ts:
                problems.append(
                    f"event {k}: span {ev['name']!r} ends at {ts} before "
                    f"it starts at {b_ts}")
    for track, stack in stacks.items():
        for b_name, _ in stack:
            problems.append(f"unclosed B {b_name!r} on track {track}")
    return problems


def validate_trace_file(path: str) -> list[str]:
    """Validate a trace JSON file (``{"traceEvents": [...]}`` or a bare
    event list)."""
    with open(path) as fh:
        doc = json.load(fh)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    if not isinstance(events, list) or not events:
        return [f"{path}: no trace events"]
    return validate_events(events)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
class Histogram:
    """Reservoir histogram with deterministic decimation past CAP samples
    (same scheme as ``PlannerStats.record_latency``)."""

    CAP = 8192
    __slots__ = ("count", "total", "vmin", "vmax", "samples")

    def __init__(self) -> None:
        self.count = 0
        self.total = 0.0
        self.vmin = float("inf")
        self.vmax = float("-inf")
        self.samples: list[float] = []

    def observe(self, v: float) -> None:
        v = float(v)
        self.count += 1
        self.total += v
        if v < self.vmin:
            self.vmin = v
        if v > self.vmax:
            self.vmax = v
        self.samples.append(v)
        if len(self.samples) > self.CAP:
            del self.samples[::2]

    def _quantile(self, srt: list[float], q: float) -> float:
        return srt[min(len(srt) - 1, int(q * len(srt)))]

    def digest(self) -> dict:
        if not self.count:
            return {"count": 0}
        srt = sorted(self.samples)
        return {
            "count": self.count,
            "mean": self.total / self.count,
            "min": self.vmin,
            "p50": self._quantile(srt, 0.50),
            "p95": self._quantile(srt, 0.95),
            "p99": self._quantile(srt, 0.99),
            "max": self.vmax,
        }


class MetricsRegistry:
    """Counters, gauges and histograms — the single sink run counters
    flow through.  All values observed here are sim-time quantities
    unless the name is prefixed ``wall.`` (see the determinism contract
    in the module docstring)."""

    def __init__(self) -> None:
        self.counters: dict[str, float] = {}
        self.gauges: dict[str, float] = {}
        self.histograms: dict[str, Histogram] = {}

    def inc(self, name: str, v: float = 1.0) -> None:
        self.counters[name] = self.counters.get(name, 0.0) + v

    def gauge(self, name: str, v: float) -> None:
        self.gauges[name] = float(v)

    def observe(self, name: str, v: float) -> None:
        h = self.histograms.get(name)
        if h is None:
            h = self.histograms[name] = Histogram()
        h.observe(v)

    def as_dict(self) -> dict:
        return {
            "counters": dict(sorted(self.counters.items())),
            "gauges": dict(sorted(self.gauges.items())),
            "histograms": {k: h.digest()
                           for k, h in sorted(self.histograms.items())},
        }


# ---------------------------------------------------------------------------
# the bundle schedulers carry
# ---------------------------------------------------------------------------
class Telemetry:
    """Tracer + metrics + per-request lifecycle log, handed to
    ``OnlineScheduler`` / ``MultiTenantScheduler`` / ``plan_fleet``.

    ``request_log=False`` keeps the trace and aggregate metrics but
    skips the per-request record list (useful at M=100k where the list
    itself is the dominant allocation).
    """

    def __init__(self, request_log: bool = True) -> None:
        self.tracer = Tracer()
        self.metrics = MetricsRegistry()
        self.request_log = request_log
        self.requests: list[dict] = []

    def record_request(self, rec: dict) -> None:
        if self.request_log:
            self.requests.append(rec)

    # -- export -------------------------------------------------------------
    def export_trace(self, path: str) -> None:
        self.tracer.export(path)

    def metrics_dict(self, planner_stats=None) -> dict:
        """Full metrics document.  Everything under ``sim_time`` derives
        from simulation-time observations; ``wall_time`` is the one
        explicitly wall-clock section (planner dispatch latency measured
        with ``perf_counter_ns``)."""
        doc: dict[str, Any] = {"sim_time": self.metrics.as_dict()}
        if self.request_log:
            doc["requests"] = self.requests
        if planner_stats is not None:
            doc["planner"] = planner_stats.as_dict()
            if planner_stats.frontier_levels:
                # per-level frontier sizes fold into a digest here so the
                # raw sample list never lands in exported JSON
                h = Histogram()
                for n in planner_stats.frontier_levels:
                    h.observe(n)
                doc["planner"]["frontier_hist"] = h.digest()
            doc["wall_time"] = {
                "planner_plan_latency": planner_stats.plan_latency(),
                "planner_fused_scan": planner_stats.fused_scan_latency(),
                "note": "perf_counter_ns wall-clock; everything else in "
                        "this document is simulation time",
            }
        return doc

    def export_metrics(self, path: str, planner_stats=None) -> None:
        with open(path, "w") as fh:
            json.dump(self.metrics_dict(planner_stats), fh, sort_keys=True,
                      indent=1)
            fh.write("\n")


# ---------------------------------------------------------------------------
# dataclass counter aggregation (fixes hand-merge drift; satellite 2)
# ---------------------------------------------------------------------------
def aggregate_counter_fields(cls, objs: Iterable[Any],
                             key: str = "aggregate") -> dict[str, Any]:
    """Sum every field of dataclass ``cls`` marked ``metadata={key: True}``
    across ``objs``.  New counters only need the metadata mark to flow
    into every aggregate — they can no longer be silently dropped from a
    hand-written merge list."""
    objs = list(objs)
    return {f.name: sum(getattr(o, f.name) for o in objs)
            for f in dataclasses.fields(cls) if f.metadata.get(key)}


# ---------------------------------------------------------------------------
# wall-clock program spans (the profiler's clock)
# ---------------------------------------------------------------------------
_LOOP, _PLAN, _OG, _EXEC = ("event loop", "planner service", "grouping DP",
                            "executor")
_IN_FLUSH = ("repro.loop.flush",)
#: where a planner call runs: a flush's plan, a stagger or channel re-plan
#: during booking, a grouping-DP level (or, on the reference DP, its fold)
_PLAN_PARENTS = ("repro.loop.flush", "repro.loop.book", "repro.og.level",
                 "repro.og.fold")

#: every program span: name -> (layer, parent spans, what it covers).  A
#: span runs inside one of its parents, or outside any span when its layer
#: is entered directly (a planner warm-up, ``run_partitioned`` called
#: alone, a compile on the cache's background thread)
WALL_SPANS: dict[str, tuple[str, tuple[str, ...], str]] = {
    "repro.loop.drain": (_LOOP, (), "one step_batch's arrival drain, the "
                         "timers it fires and the flush shape's prefetch"),
    "repro.loop.flush": (_LOOP, (), "one flush, from its queue to its "
                         "on_flush hook (flush=seq, batch=requests)"),
    "repro.loop.book": (_LOOP, _IN_FLUSH, "everything after the flush's plan "
                        "up to on_flush: stagger, post-plan, actualize, "
                        "energy accounting, booking, the FlushEvent"),
    "repro.plan.dispatch": (_PLAN, _PLAN_PARENTS, "plan_async: padding, "
                            "executable lookup, device launch"),
    "repro.plan.fetch": (_PLAN, _PLAN_PARENTS, "the device-to-host copy of "
                         "a plan call's outputs, waiting on the device"),
    "repro.plan.reconstruct": (_PLAN, _PLAN_PARENTS, "host reconstruction "
                               "of each group's schedule"),
    "repro.plan.compile": (_PLAN, ("repro.plan.dispatch",), "a planner "
                           "executable compile, on whatever thread it runs"),
    "repro.og.plan": (_OG, (), "one optimal grouping (users=M)"),
    "repro.og.level": (_OG, ("repro.og.plan",), "one DP level (level=j): "
                       "its solves and its fold"),
    "repro.og.segments": (_OG, ("repro.og.level",), "the level's solve list "
                          "and the sub-fleets of its segments"),
    "repro.og.fold": (_OG, ("repro.og.level",), "the host fold of the "
                      "level's solved segments into DP states"),
    "repro.exec.prepare": (_EXEC, _IN_FLUSH, "token stack, embedding "
                           "dispatch, the output taken from the executor's "
                           "host pool (reused=1: an earlier output's "
                           "memory, its pages already resident)"),
    "repro.exec.split": (_EXEC, _IN_FLUSH, "the row gathers of one side "
                         "(local or offloaded)"),
    "repro.exec.blocks": (_EXEC, _IN_FLUSH, "the layer-step dispatches of "
                          "one pass (lo=, hi=)"),
    "repro.exec.head": (_EXEC, _IN_FLUSH, "the head-step dispatch"),
    "repro.exec.wait": (_EXEC, _IN_FLUSH, "block_until_ready on the head "
                        "output: device work still outstanding"),
    "repro.exec.to_host": (_EXEC, _IN_FLUSH, "the logits copy to the host "
                           "(bytes=)"),
    "repro.exec.scatter": (_EXEC, _IN_FLUSH, "the logits rows written into "
                           "the output"),
}


#: the shared no-op span while no profiler runs
_NO_SPAN = contextlib.nullcontext()
_TraceAnnotation = None


def span(name: str, **args):
    """A wall-clock program span: ``with span("repro.exec.wait"): ...``.
    ``name`` is a key of :data:`WALL_SPANS`; ``args`` (ints, floats,
    strings) tie spans together (``flush=``, ``level=``, ``bytes=``) and
    land as event stats in the profiler's trace.  With no profiler
    running the span is one shared no-op."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        # jax imported on first use: the simulator's users pay nothing
        from jax.profiler import TraceAnnotation as _TraceAnnotation
    if not _TraceAnnotation.is_enabled():
        return _NO_SPAN
    return _TraceAnnotation(name, **args)
