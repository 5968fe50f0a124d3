"""Telemetry subsystem: tracing never perturbs results (bit-identical on
vs off), traces are schema-valid, causally sane and byte-stable, the
fields-metadata-driven counter aggregation round-trips every field, and
the wall-clock program spans land in a profiler capture under their
catalog parents."""
import dataclasses
import functools
import glob
import json
import re
from pathlib import Path

import numpy as np
import pytest

from repro.core import (MultiTenantScheduler, OnlineArrival, OnlineResult,
                        OnlineScheduler, PlannerService, PlannerStats,
                        Schedule, Telemetry, Tenant,
                        aggregate_counter_fields, make_channel,
                        make_edge_profile, make_fleet, mobilenet_v2_profile,
                        poisson_arrivals, validate_events)
from repro.core.telemetry import (NULL_TRACER, TID_GPU, WALL_SPANS,
                                  Histogram, MetricsRegistry, Tracer, span,
                                  tenant_tid)

PROF = mobilenet_v2_profile()
EDGE = make_edge_profile(PROF)

POLICIES = ("immediate", "window", "slack", "lastcall")


def _assert_same_result(a, b):
    assert a.energy == b.energy
    assert a.n_flushes == b.n_flushes
    assert a.batch_sizes == b.batch_sizes
    assert a.violations == b.violations
    assert a.flush_times == b.flush_times
    assert a.f_edges == b.f_edges
    np.testing.assert_array_equal(a.per_user_energy, b.per_user_energy)


def _run_online(telemetry, *, policy="slack", occupancy="serialized",
                plan_workers=0, batched=False, channel=None, M=8,
                rate=200.0, seed=0):
    fleet = make_fleet(M, PROF, EDGE, beta=20.0, seed=seed)
    arrivals = poisson_arrivals(M, rate, fleet, seed=seed)
    sched = OnlineScheduler(PROF, fleet, EDGE, policy=policy, window=0.02,
                            occupancy=occupancy, channel=channel,
                            plan_workers=plan_workers, telemetry=telemetry)
    sched.submit_many(arrivals)
    res = sched.run_batched() if (batched or plan_workers) else sched.run()
    return sched, res


# ---------------------------------------------------------------------------
# tracing on vs off: bit-identical results (the overhead contract's twin)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("occupancy", ["serialized", "interleaved"])
def test_tracing_parity_policies_and_occupancy(policy, occupancy):
    _, off = _run_online(None, policy=policy, occupancy=occupancy)
    tel = Telemetry()
    _, on = _run_online(tel, policy=policy, occupancy=occupancy)
    _assert_same_result(off, on)
    assert validate_events(tel.tracer.events) == []


@pytest.mark.parametrize("plan_workers", [0, 2])
def test_tracing_parity_batched_loop(plan_workers):
    _, off = _run_online(None, batched=True, plan_workers=plan_workers)
    tel = Telemetry()
    _, on = _run_online(tel, batched=True, plan_workers=plan_workers)
    _assert_same_result(off, on)
    assert validate_events(tel.tracer.events) == []


def test_tracing_parity_with_channel():
    ch_off = make_channel("trace", seed=7)
    ch_on = make_channel("trace", seed=7)
    _, off = _run_online(None, channel=ch_off, rate=500.0, seed=3)
    tel = Telemetry()
    _, on = _run_online(tel, channel=ch_on, rate=500.0, seed=3)
    _assert_same_result(off, on)
    assert validate_events(tel.tracer.events) == []


def _mts_result_fields(r):
    return (r.energy, r.violations, r.preemptions, r.bookings,
            r.gpu_busy_until, r.gap_fills, r.dvfs_rescales,
            r.dvfs_energy_saved, r.upload_error, r.channel_replans,
            r.realized_late, r.stagger_replans, r.pruned_probes,
            [t.degraded for t in r.tenants],
            [t.rejected for t in r.tenants],
            [t.preempt_tax_inflicted for t in r.tenants])


def _run_tenants(telemetry, *, admission="degrade", preemption=True,
                 Tb=0.06):
    fleetA = make_fleet(8, PROF, EDGE, beta=30.0, seed=0)
    fleetB = make_fleet(2, PROF, EDGE, beta=3.0, seed=1)
    A = Tenant(PROF, fleetA, EDGE, name="A", policy="immediate")
    B = Tenant(PROF, fleetB, EDGE, name="B", policy="immediate")
    trA = ([OnlineArrival(m, 0.0, float(fleetA.deadline[m]))
            for m in range(4)]
           + [OnlineArrival(m, 1e-4, float(fleetA.deadline[m]))
              for m in range(4, 8)])
    trB = [OnlineArrival(0, 2e-4, Tb)]
    mts = MultiTenantScheduler([A, B], preemption=preemption,
                               admission=admission, telemetry=telemetry)
    mts.submit_traces([trA, trB])
    return mts, mts.run()


def test_tracing_parity_multi_tenant_with_preemption():
    """The preemption-forcing scenario (what-if trials, victim replans,
    admission control armed) must play out identically traced."""
    _, off = _run_tenants(None)
    tel = Telemetry()
    _, on = _run_tenants(tel)
    assert off.preemptions >= 1          # the scenario actually preempts
    assert _mts_result_fields(off) == _mts_result_fields(on)
    for a, b in zip(off.tenants, on.tenants):
        _assert_same_result(a.result, b.result)
    assert validate_events(tel.tracer.events) == []
    names = {e["name"] for e in tel.tracer.events}
    assert "preempt.commit" in names
    assert "preempt.victim" in names


# ---------------------------------------------------------------------------
# trace content: causal sanity, reservation geometry, determinism
# ---------------------------------------------------------------------------

def test_trace_spans_causal_and_reservations_match_geometry():
    tel = Telemetry()
    sched, res = _run_online(tel, occupancy="interleaved", rate=500.0)
    events = tel.tracer.events
    assert validate_events(events) == []
    for ev in events:
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0
    # every FINAL reservation has a GPU-track span with its exact
    # geometry (preempted/stretched intermediates may leave historical
    # spans; unstretch emits a corrective span for the final shape)
    gpu_spans = [(e["ts"], e["ts"] + e["dur"]) for e in events
                 if e["ph"] == "X" and e["tid"] == TID_GPU]
    for r in sched.timeline.reservations:
        assert (r.gpu_start * 1e6, r.end * 1e6) in gpu_spans, \
            f"reservation {r.gpu_start}-{r.end} has no matching span"


def test_trace_flush_and_request_lifecycle_recorded():
    tel = Telemetry()
    sched, res = _run_online(tel)
    names = [e["name"] for e in tel.tracer.events]
    assert names.count("arrival") == sched.fleet.M
    assert names.count("flush") == res.n_flushes
    assert sum(n.startswith("req u") for n in names) == sched.fleet.M
    # lifecycle records: one per request, causally ordered sim times
    assert len(tel.requests) == sched.fleet.M
    for rec in tel.requests:
        assert rec["arrival"] <= rec["flushed"] <= rec["done"]
        if rec["offloaded"]:
            assert rec["flushed"] <= rec["gpu_start"] <= rec["done"]
        else:
            assert rec["gpu_start"] is None
    assert tel.metrics.counters["loop.arrivals"] == sched.fleet.M
    assert tel.metrics.counters["loop.flushes"] == res.n_flushes


def test_trace_is_byte_stable_for_fixed_seed(tmp_path):
    """Golden-trace determinism: two identical runs export identical
    bytes (all timestamps sim-time; no wall-clock leaks into the trace)."""
    paths = []
    for k in range(2):
        tel = Telemetry()
        _run_online(tel, policy="window", rate=300.0, seed=5)
        p = tmp_path / f"trace{k}.json"
        tel.export_trace(str(p))
        paths.append(p)
    b0, b1 = paths[0].read_bytes(), paths[1].read_bytes()
    assert b0 == b1
    # and it parses back as Chrome trace JSON with the required keys
    doc = json.loads(b0)
    assert doc["traceEvents"]
    assert validate_events(doc["traceEvents"]) == []


def test_null_tracer_is_inert_and_shared():
    assert NULL_TRACER.enabled is False
    assert not hasattr(NULL_TRACER, "__dict__")      # __slots__: no allocs
    NULL_TRACER.instant("x", 0.0, 1, {"a": 1})       # all no-ops
    NULL_TRACER.span("x", 0.0, 1.0, 1)
    sched = OnlineScheduler(PROF, make_fleet(2, PROF, EDGE, beta=20.0,
                                             seed=0), EDGE)
    assert sched._tr is NULL_TRACER
    assert sched.timeline.tracer is NULL_TRACER


def test_tenant_tid_disjoint_from_fixed_tracks():
    from repro.core.telemetry import (TID_PLANNER, TID_RUN, TID_UPLINK)
    fixed = {TID_RUN, TID_GPU, TID_UPLINK, TID_PLANNER}
    assert all(tenant_tid(k) not in fixed for k in range(100))
    assert tenant_tid(3) != tenant_tid(4)


# ---------------------------------------------------------------------------
# validator negatives: each invariant actually trips
# ---------------------------------------------------------------------------

def _ev(**kw):
    base = {"ph": "i", "ts": 0.0, "pid": 1, "tid": 1, "name": "x"}
    base.update(kw)
    return base


def test_validator_catches_schema_violations():
    assert validate_events([{"ph": "i", "ts": 0.0}])         # missing keys
    assert validate_events([_ev(ph="X")])                    # X without dur
    assert validate_events([_ev(ph="X", dur=-1.0)])          # negative dur
    assert validate_events([_ev(ph="E")])                    # E without B
    assert validate_events([_ev(ph="B", name="a"),           # name mismatch
                            _ev(ph="E", name="b")])
    assert validate_events([_ev(ph="B", ts=2.0),             # E before B
                            _ev(ph="E", ts=1.0)])
    assert validate_events([_ev(ph="B")])                    # unclosed B
    assert validate_events([_ev(ph="B"), _ev(ph="E")]) == []  # clean pair


def test_tracer_nesting_across_tracks_is_independent():
    tr = Tracer()
    tr.begin("run", 0.0, 1)
    tr.span("batch", 0.5, 1.0, 2)
    tr.end("run", 2.0, 1)
    assert validate_events(tr.events) == []


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_metrics_registry_counters_gauges_digests():
    m = MetricsRegistry()
    m.inc("a")
    m.inc("a", 2.0)
    m.gauge("g", 7.5)
    for v in range(100):
        m.observe("h", float(v))
    d = m.as_dict()
    assert d["counters"]["a"] == 3.0
    assert d["gauges"]["g"] == 7.5
    h = d["histograms"]["h"]
    assert h["count"] == 100 and h["min"] == 0.0 and h["max"] == 99.0
    assert h["p50"] == 50.0 and h["p99"] == 99.0


def test_histogram_decimation_keeps_exact_count_min_max():
    h = Histogram()
    n = h.CAP * 3
    for v in range(n):
        h.observe(float(v))
    d = h.digest()
    assert d["count"] == n and d["min"] == 0.0 and d["max"] == n - 1
    assert len(h.samples) <= h.CAP + 1
    # decimation keeps exact count/min/max; quantiles stay ordered and
    # in range (they are recency-biased by design, not unbiased)
    assert d["min"] <= d["p50"] <= d["p95"] <= d["p99"] <= d["max"]


# ---------------------------------------------------------------------------
# satellite 2: fields-metadata-driven counter aggregation round-trips
# ---------------------------------------------------------------------------

def test_planner_stats_merge_round_trips_every_field():
    a, b = PlannerStats(), PlannerStats()
    # give EVERY field a distinct nonzero value so a dropped field shows
    for k, f in enumerate(dataclasses.fields(PlannerStats)):
        if f.name == "plan_ns":
            a.plan_ns, b.plan_ns = [10, 30], [20]
            continue
        setattr(a, f.name, 3 + k)
        setattr(b, f.name, 5 + 2 * k)
    m = a.merge(b)
    for f in dataclasses.fields(PlannerStats):
        how = f.metadata.get("merge", "sum")
        av, bv = getattr(a, f.name), getattr(b, f.name)
        got = getattr(m, f.name)
        if f.name == "plan_ns":
            assert got == [10, 30, 20]
        elif how == "sum":
            assert got == av + bv, f.name
        elif how == "max":
            assert got == max(av, bv), f.name
        elif how == "min_counted":
            assert got == min(av, bv), f.name


def test_planner_stats_min_counted_ignores_uncounted_side():
    a = PlannerStats()
    b = PlannerStats()
    b.record_latency(500)
    m = a.merge(b)          # a never planned: its zero min must not win
    assert m.plan_ns_min == 500
    assert a.merge(a).plan_ns_min == 0


def test_planner_stats_as_dict_exports_all_but_opted_out():
    s = PlannerStats()
    s.record_latency(1000)
    d = s.as_dict()
    for f in dataclasses.fields(PlannerStats):
        if f.metadata.get("export", True):
            assert f.name in d, f.name
        else:
            assert f.name not in d, f.name
    assert d["plan_latency"]["count"] == 1


def test_online_result_counters_aggregate_by_metadata():
    marked = [f.name for f in dataclasses.fields(OnlineResult)
              if f.metadata.get("aggregate")]
    assert set(marked) == {"upload_error", "channel_replans",
                           "realized_late", "stagger_replans",
                           "pruned_probes"}
    rs = []
    for k in range(2):
        r = OnlineResult.__new__(OnlineResult)
        for f in dataclasses.fields(OnlineResult):
            setattr(r, f.name, None)
        for j, name in enumerate(marked):
            setattr(r, name, (k + 1) * (j + 2))
        rs.append(r)
    agg = aggregate_counter_fields(OnlineResult, rs)
    assert set(agg) == set(marked)
    for j, name in enumerate(marked):
        assert agg[name] == 3 * (j + 2)


def test_multi_tenant_result_sums_per_scheduler_counters():
    """The arbiter's aggregate loop counters equal the per-tenant sums
    (the field-driven aggregation replacing the hand-written merge)."""
    _, r = _run_tenants(None)
    for name in ("upload_error", "channel_replans", "realized_late",
                 "stagger_replans", "pruned_probes"):
        assert getattr(r, name) == sum(getattr(t.result, name)
                                       for t in r.tenants), name


def test_metrics_document_separates_wall_time(tmp_path):
    tel = Telemetry()
    sched, _ = _run_online(tel)
    stats = sched.service.stats()
    doc = tel.metrics_dict(planner_stats=stats)
    assert "sim_time" in doc and "wall_time" in doc
    assert "planner_plan_latency" in doc["wall_time"]
    # nothing wall-clock outside the wall_time section: the sim_time
    # counters are all sim quantities (pinned by the byte-stable trace
    # test); here we pin the document shape and JSON round-trip
    p = tmp_path / "metrics.json"
    tel.export_metrics(str(p), planner_stats=stats)
    back = json.loads(p.read_text())
    assert back["wall_time"]["note"].startswith("perf_counter_ns")


# ---------------------------------------------------------------------------
# wall-clock program spans on the profiler's clock
# ---------------------------------------------------------------------------

SRC = Path(__file__).resolve().parents[2] / "src" / "repro"


def _one_flush():
    """One ``step_batch`` flush of a small online fleet."""
    fleet = make_fleet(8, PROF, EDGE, beta=20.0, seed=0)
    sched = OnlineScheduler(PROF, fleet, EDGE, policy="slack")
    sched.submit_many(poisson_arrivals(8, 200.0, fleet, seed=0))
    ev = sched.step_batch()
    s = ev.schedule
    return (ev.time, ev.users.tolist(), ev.gpu_free, s.energy, s.partition,
            s.f_edge, s.offload.tolist(), s.f_device.tolist())


def _plan_six():
    """A ``plan_fleet`` of 6 users through the grouping DP."""
    fleet = make_fleet(6, PROF, EDGE, beta=5.0, seed=3)
    g = PlannerService(PROF, EDGE).plan_fleet(fleet)
    return (g.energy, [x.tolist() for x in g.groups],
            [(s.energy, s.partition, s.offload.tolist()) for s in g.schedules])


@functools.cache
def _executor():
    """One 2-layer executor, so that repeated calls share its output pool."""
    import jax
    from repro.configs import ARCHS
    from repro.models import init_params
    from repro.serving import BlockwiseExecutor
    cfg = ARCHS["glm4-9b"].reduced()
    assert cfg.num_layers == 2
    return BlockwiseExecutor(cfg, init_params(cfg, jax.random.PRNGKey(0)))


def _partitioned():
    """``run_partitioned`` on a 2-layer model, one local and two
    offloaded users split after layer 1."""
    from repro.serving import Request
    from repro.serving.server import run_partitioned
    ex = _executor()
    cfg = ex.cfg
    rng = np.random.default_rng(0)
    reqs = [Request(user=m, deadline=1.0,
                    tokens=rng.integers(0, cfg.vocab_size, 8, dtype=np.int32))
            for m in range(3)]
    sched = Schedule(feasible=True, energy=0.0, partition=1, f_edge=1e9,
                     offload=np.array([True, False, True]),
                     f_device=np.ones(3), t_free_end=0.0, terms={},
                     per_user_energy=np.zeros(3))
    return run_partitioned(ex, cfg.vocab_size, reqs, sched)


_LOOP = {"repro.loop.drain", "repro.loop.flush", "repro.loop.book"}
_PLAN = {"repro.plan.dispatch", "repro.plan.fetch", "repro.plan.reconstruct"}
_OG = {"repro.og.plan", "repro.og.level", "repro.og.segments",
       "repro.og.fold"}
_EXEC = {"repro.exec.prepare", "repro.exec.split", "repro.exec.blocks",
         "repro.exec.head", "repro.exec.wait", "repro.exec.to_host",
         "repro.exec.scatter"}
PATHS = {"flush": (_one_flush, _LOOP | _PLAN),
         "plan_fleet": (_plan_six, _OG | _PLAN),
         "run_partitioned": (_partitioned, _EXEC)}


def _profiled(fn, logdir):
    """``fn()`` under a profiler capture: its result and the capture's
    ``repro.*`` host events as ``(name, start, end, thread, args)``."""
    import jax
    with jax.profiler.trace(str(logdir)):
        out = fn()
    path, = glob.glob(str(logdir / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                events += [(ev.name, ev.start_ns, ev.end_ns, k,
                            dict(ev.stats)) for ev in line.events
                           if ev.name.startswith("repro.")]
    return out, events


def _parent(ev, events):
    """The innermost ``repro.*`` event on ``ev``'s thread holding it."""
    best = None
    for other in events:
        if (other is not ev and other[3] == ev[3] and other[1] <= ev[1]
                and ev[2] <= other[2]
                and (best is None or other[1] >= best[1])):
            best = other
    return None if best is None else best[0]


@pytest.mark.parametrize("path", sorted(PATHS))
def test_profiled_run_has_catalog_spans_and_same_results(path, tmp_path):
    fn, expected = PATHS[path]
    plain = fn()                          # also warms every shape
    out, events = _profiled(fn, tmp_path)
    if isinstance(plain, np.ndarray):
        np.testing.assert_array_equal(out, plain)
    else:
        assert out == plain
    names = {ev[0] for ev in events}
    assert names <= set(WALL_SPANS), names - set(WALL_SPANS)
    assert expected <= names, expected - names
    for ev in events:
        parent = _parent(ev, events)
        assert parent is None or parent in WALL_SPANS[ev[0]][1], \
            (ev[0], parent)
    if path == "run_partitioned":
        assert sum(ev[4]["bytes"] for ev in events
                   if ev[0] == "repro.exec.to_host") == out.nbytes
        # ``plain`` still holds the first output, so this one is new memory
        assert _reused(events) == [0]
        del plain, out
        out, events = _profiled(fn, tmp_path / "again")
        assert _reused(events) == [1]


def _reused(events):
    return [ev[4]["reused"] for ev in events
            if ev[0] == "repro.exec.prepare"]


def test_wall_span_catalog_is_closed():
    layers = {layer for layer, _, _ in WALL_SPANS.values()}
    assert layers == {"event loop", "planner service", "grouping DP",
                      "executor"}
    for name, (_, parents, what) in WALL_SPANS.items():
        assert name.startswith("repro.") and what
        assert set(parents) <= set(WALL_SPANS), name


def test_every_span_site_names_a_catalog_span():
    """A span name the catalog lacks is a bug, on paths no test runs too."""
    sites = set()
    for f in SRC.rglob("*.py"):
        sites |= set(re.findall(r'span\(\s*"(repro\.[\w.]+)"',
                                f.read_text()))
    assert sites and sites <= set(WALL_SPANS), sites - set(WALL_SPANS)
    assert set(WALL_SPANS) <= sites, set(WALL_SPANS) - sites


def test_span_without_profiler_is_a_shared_noop():
    a, b = span("repro.loop.flush", flush=1), span("repro.og.fold")
    assert a is b
    with a:
        pass
