"""Fleet-scale serving benchmark: the batched event loop and the
hierarchical/incremental planners at M = 1k / 10k / 100k users.

Two sections, one JSON document (``BENCH_scale.json``):

* **online** — a sustained Poisson stream over an M-user fleet drained
  through :meth:`~repro.core.OnlineScheduler.run_batched` (the fleet-scale
  event loop: arrival runs drain in one pass, plan arrays stay
  device-resident, flush shapes prefetch on the background compile pool).
  Reports goodput (deadline-meeting requests per second of makespan),
  energy per request, planner dispatch latency percentiles
  (:meth:`~repro.core.PlannerStats.plan_latency`) and wall time.  The
  arrival rate scales with M (``--load`` requests/s per user) so the flush
  cadence — and therefore wall time — stays roughly M-independent while
  batch sizes grow with the fleet.

* **pipelined** — the same online runs with ``plan_workers`` plan-ahead
  threads overlapping the next flush's grouping solve with the current
  batch's bookkeeping.  Results are asserted bitwise-equal to the
  synchronous rows (speculation is consumed only on exact key match), so
  the only thing that may move is wall time: ``pipeline_speedup`` and the
  plan-ahead hit rate are reported per M.

* **dynamic** — one SharedUplink channel-aware run (sync + pipelined
  twin) at M = min(10k, max fleet): channel-keyed speculation must land
  (nonzero plan-ahead hits), win wall time, and stay bitwise against the
  synchronous twin — the configuration PR 7 had to disable outright.
  The pair shares one fresh executable cache, so its sync twin carries
  the cold in-process compile like every per-M pipelined comparison,
  without warming the process-wide cache under the traced-overhead rows.

* **traced** — the same online runs with the full telemetry stack
  attached (event tracer + metrics registry + per-request lifecycle
  records).  Sim results are asserted bitwise-equal to the untraced
  twin — tracing observes, never perturbs — so the only number that may
  move is wall time: ``trace_overhead`` is the ratio the nightly
  regression gate bounds (``check_regression.py --trace-overhead-max``).

* **planning** — the one-shot OG problem at a fleet size where the exact
  O(M²)-segment DP is measurably expensive: prefix-exact vs the
  Pareto-frontier DP (sound under occupancy coupling; energy must come
  out ``<=`` prefix) vs the adaptive self-sizing beam
  (``beam_width="auto"``: energy ``<=`` prefix by the anchor invariant,
  ``>= 90%`` of the full-frontier win at lower wall time) vs
  hierarchical :func:`~repro.core.cohort_grouping`
  (wall time + energy band — banded against BOTH baselines; only the
  pareto band is one-sided), and :class:`~repro.core.IncrementalOgState`
  fleet churn (a late-deadline arrival re-folds O(1) DP levels; a mid
  departure re-folds the suffix) against the from-scratch re-solve, with
  bit-parity asserted.

The committed ``BENCH_scale.json`` is the regression baseline
``benchmarks/check_regression.py --scale-baseline`` gates against
(goodput must not drop, energy/request must not grow beyond tolerance).
``--dry-run`` shrinks every axis to CI-smoke size and diverts the default
output path so the baseline snapshot is never clobbered.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time

import numpy as np


def _build(M: int, seed: int):
    from repro.core import make_edge_profile, make_fleet, mobilenet_v2_profile
    profile = mobilenet_v2_profile()
    edge = make_edge_profile(profile)
    fleet = make_fleet(M, profile, edge, beta=(10.0, 30.0), seed=seed)
    return profile, edge, fleet


def run_online_scale(M: int, load_hz: float, seed: int, arrival_seed: int,
                     policy: str = "slack",
                     batch_window: float = 0.0,
                     plan_workers: int = 0,
                     plan_depth: int = 1,
                     channel: str | None = None,
                     telemetry=None, cache=None):
    """One sustained-load run at fleet size M through the batched loop.

    Returns ``(row, result)`` — the JSON row plus the raw
    :class:`OnlineResult` so the pipelined run can be asserted bitwise
    equal to the synchronous one.  ``telemetry`` attaches a
    :class:`~repro.core.Telemetry` sink (the traced section measures its
    overhead and asserts result parity against the untraced twin).
    ``channel`` names a :func:`~repro.core.make_channel` kind for a
    channel-aware run (the dynamic-channel pipelined section exercises
    digest-keyed speculation); ``plan_depth`` sets the speculation chain
    depth when ``plan_workers > 0``.  ``cache`` gives the run's planner
    service that executable cache instead of the process-wide one."""
    from repro.core import (OnlineScheduler, PlannerService, make_channel,
                            poisson_arrivals)
    profile, edge, fleet = _build(M, seed)
    rate = load_hz * M
    arrivals = poisson_arrivals(M, rate, fleet, seed=arrival_seed)
    service = PlannerService(profile, edge, cache=cache)
    sched = OnlineScheduler(profile, fleet, edge, policy=policy,
                            keep_frac=0.7, service=service,
                            batch_window=batch_window,
                            plan_workers=plan_workers,
                            plan_depth=plan_depth,
                            channel=(make_channel(channel)
                                     if channel else None),
                            telemetry=telemetry)
    sched.submit_many(sorted(arrivals, key=lambda a: a.arrival))
    t0 = time.perf_counter()
    res = sched.run_batched()
    wall = time.perf_counter() - t0
    makespan = max(res.flush_times) if res.flush_times else 0.0
    served = M - res.violations
    stats = service.stats()
    lat = stats.plan_latency()
    row = dict(
        users=M, rate_hz=rate, policy=policy, seed=seed,
        arrival_seed=arrival_seed, batch_window=batch_window,
        plan_workers=plan_workers, plan_depth=plan_depth,
        channel=channel,
        n_flushes=res.n_flushes,
        mean_batch=float(np.mean(res.batch_sizes)) if res.batch_sizes else 0.0,
        max_batch=max(res.batch_sizes) if res.batch_sizes else 0,
        violations=res.violations,
        energy=res.energy,
        energy_per_request=res.energy / M,
        makespan_s=makespan,
        goodput_rps=served / makespan if makespan > 0 else 0.0,
        wall_s=wall,
        plan_latency=lat,
        plan_ahead_hits=stats.plan_ahead_hits,
        plan_ahead_misses=stats.plan_ahead_misses,
        # the loop is only "batched" if batching actually emerged AND the
        # fleet was served (not a degenerate all-violations run)
        healthy=bool(res.n_flushes < M and served > 0.5 * M),
    )
    service.close()
    return row, res


def _same_result(a, b) -> bool:
    """Bitwise parity across every simulated quantity (wall time aside)."""
    return bool(a.energy == b.energy and a.n_flushes == b.n_flushes
                and a.batch_sizes == b.batch_sizes
                and a.violations == b.violations
                and a.flush_times == b.flush_times
                and a.f_edges == b.f_edges
                and np.array_equal(a.per_user_energy, b.per_user_energy))


def run_planning_scale(M: int, cohort_size: int, seed: int) -> dict:
    """Exact vs cohort OG and incremental churn at one fleet size.

    The service is shared across every solve so compiled planner shapes
    amortize exactly as they do in the serving layer; the exact solve runs
    FIRST so its timing carries the compile cost (cohort and incremental
    then measure algorithmic work, which is what scales with M)."""
    from repro.core import (IncrementalOgState, PlannerService,
                            cohort_grouping, make_fleet, optimal_grouping)
    profile, edge, fleet = _build(M, seed)
    service = PlannerService(profile, edge)

    t0 = time.perf_counter()
    exact = optimal_grouping(profile, fleet, edge, service=service)
    t_exact = time.perf_counter() - t0
    t0 = time.perf_counter()
    pareto = optimal_grouping(profile, fleet, edge, service=service,
                              dp="pareto")
    t_pareto = time.perf_counter() - t0
    fstats = service.stats()
    bw0 = fstats.beam_widenings
    t0 = time.perf_counter()
    adaptive = optimal_grouping(profile, fleet, edge, service=service,
                                dp="pareto", beam_width="auto")
    t_adaptive = time.perf_counter() - t0
    beam_widenings = service.stats().beam_widenings - bw0
    full_win = exact.energy - pareto.energy
    adaptive_win_frac = ((exact.energy - adaptive.energy) / full_win
                         if full_win > 1e-12 else 1.0)
    t0 = time.perf_counter()
    cohort = cohort_grouping(profile, fleet, edge, cohort_size=cohort_size,
                             service=service)
    t_cohort = time.perf_counter() - t0
    band = cohort.energy / exact.energy - 1.0
    t0 = time.perf_counter()
    cohort_pareto = cohort_grouping(profile, fleet, edge,
                                    cohort_size=cohort_size,
                                    service=service, dp="pareto")
    t_cohort_pareto = time.perf_counter() - t0
    band_pareto = cohort_pareto.energy / pareto.energy - 1.0

    state = IncrementalOgState(profile, fleet, edge, service=service)
    t0 = time.perf_counter()
    state.plan()
    t_seed = time.perf_counter() - t0
    # a late-deadline arrival sorts to the tail: O(1) levels re-fold
    tail_row = make_fleet(1, profile, edge, beta=60.0, seed=seed + 1)
    t0 = time.perf_counter()
    p_arrive = state.arrive(tail_row)
    t_arrive = time.perf_counter() - t0
    arrive_levels = state.last_refold_levels
    t0 = time.perf_counter()
    p_depart = state.depart(state.M // 2)
    t_depart = time.perf_counter() - t0
    depart_levels = state.last_refold_levels
    t0 = time.perf_counter()
    scratch = optimal_grouping(profile, state.fleet, edge, service=service)
    t_scratch = time.perf_counter() - t0
    assert p_depart.energy == scratch.energy, \
        "incremental OG diverged from the from-scratch solve"

    # churn fast path under the adaptive-beam pareto DP: a churn-free
    # repeat plan() must be memoized (zero levels re-folded, same object)
    # and arrive/depart must rewind the beam history and still match the
    # from-scratch adaptive solve bitwise
    pstate = IncrementalOgState(profile, fleet, edge, service=service,
                                dp="pareto", beam_width="auto")
    t0 = time.perf_counter()
    pp_seed = pstate.plan()
    t_pseed = time.perf_counter() - t0
    t0 = time.perf_counter()
    pp_repeat = pstate.plan()
    t_prepeat = time.perf_counter() - t0
    repeat_memoized = bool(pp_repeat is pp_seed
                           and pstate.last_refold_levels == 0)
    t0 = time.perf_counter()
    pstate.arrive(tail_row)
    t_parrive = time.perf_counter() - t0
    parrive_levels = pstate.last_refold_levels
    t0 = time.perf_counter()
    pp_depart = pstate.depart(pstate.M // 2)
    t_pdepart = time.perf_counter() - t0
    pscratch = optimal_grouping(profile, pstate.fleet, edge,
                                service=service, dp="pareto",
                                beam_width="auto")
    return dict(
        users=M, cohort_size=cohort_size, seed=seed,
        exact_s=t_exact, exact_energy=exact.energy,
        pareto_s=t_pareto, pareto_energy=pareto.energy,
        pareto_vs_prefix=pareto.energy / exact.energy - 1.0,
        pareto_sound=bool(pareto.energy <= exact.energy + 1e-12),
        adaptive_s=t_adaptive, adaptive_energy=adaptive.energy,
        adaptive_win_frac=adaptive_win_frac,
        adaptive_sound=bool(adaptive.energy <= exact.energy + 1e-12),
        adaptive_vs_pareto_wall=(t_adaptive / t_pareto
                                 if t_pareto > 0 else 0.0),
        adaptive_vs_prefix_wall=(t_adaptive / t_exact
                                 if t_exact > 0 else 0.0),
        beam_widenings=beam_widenings,
        frontier_states=fstats.frontier_states,
        frontier_max=fstats.frontier_max,
        dominance_pruned=fstats.dominance_pruned,
        cohort_s=t_cohort, cohort_energy=cohort.energy,
        cohort_energy_band=band,
        cohort_pareto_s=t_cohort_pareto,
        cohort_pareto_energy=cohort_pareto.energy,
        cohort_energy_band_vs_pareto=band_pareto,
        cohort_speedup=t_exact / t_cohort if t_cohort > 0 else 0.0,
        incremental_seed_s=t_seed,
        arrive_s=t_arrive, arrive_refold_levels=arrive_levels,
        depart_s=t_depart, depart_refold_levels=depart_levels,
        scratch_s=t_scratch,
        arrive_speedup=t_scratch / t_arrive if t_arrive > 0 else 0.0,
        incremental_parity=bool(p_depart.energy == scratch.energy),
        tail_arrival_cheap=bool(arrive_levels <= 2),
        pareto_churn_seed_s=t_pseed,
        pareto_churn_repeat_s=t_prepeat,
        pareto_churn_repeat_memoized=repeat_memoized,
        pareto_arrive_s=t_parrive,
        pareto_arrive_refold_levels=parrive_levels,
        pareto_depart_s=t_pdepart,
        pareto_churn_parity=bool(pp_depart.energy == pscratch.energy),
    )


def run_dynamic_channel(m_dyn: int, load: float, seed: int,
                        arrival_seed: int, policy: str,
                        batch_window: float, plan_workers: int,
                        plan_depth: int) -> dict:
    """The dynamic-channel pipelined pair: a SharedUplink channel-aware
    sync run and its plan-ahead twin.  Speculation under a dynamic
    channel-aware snapshot is keyed on the channel digest, so this run
    must show nonzero plan-ahead hits AND a wall-time win, still bitwise
    against the synchronous twin.  The pair
    shares one fresh executable cache, so the sync twin carries the cold
    in-process compile — the same convention as the per-M pipelined rows
    (overlapping first-dispatch compiles is the win) — without warming
    the process-wide cache the other sections use."""
    from repro.core.jdob import ExecutableCache
    cache = ExecutableCache()
    try:
        rd, resd = run_online_scale(m_dyn, load, seed, arrival_seed,
                                    policy=policy, batch_window=batch_window,
                                    channel="shared", cache=cache)
        rdp, resdp = run_online_scale(m_dyn, load, seed, arrival_seed,
                                      policy=policy,
                                      batch_window=batch_window,
                                      plan_workers=plan_workers,
                                      plan_depth=plan_depth,
                                      channel="shared", cache=cache)
    finally:
        cache.shutdown()
    rdp["parity"] = _same_result(resd, resdp)
    rdp["pipeline_speedup"] = (rd["wall_s"] / rdp["wall_s"]
                               if rdp["wall_s"] > 0 else 0.0)
    return dict(sync=rd, pipelined=rdp)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fleet-sizes", type=int, nargs="+",
                    default=[1000, 10000, 100000],
                    help="online-section fleet sizes M")
    ap.add_argument("--load", type=float, default=2.0,
                    help="arrival rate per user (requests/s); the stream "
                         "rate is load*M so flush cadence stays "
                         "M-independent")
    ap.add_argument("--policy", default="slack",
                    choices=["immediate", "window", "slack", "lastcall"])
    ap.add_argument("--batch-window", type=float, default=0.0)
    ap.add_argument("--plan-workers", type=int, default=2,
                    help="plan-ahead threads for the pipelined section "
                         "(0 skips it)")
    ap.add_argument("--plan-depth", type=int, default=2,
                    help="speculation chain depth for the pipelined and "
                         "dynamic-channel sections")
    ap.add_argument("--planning-users", type=int, default=96,
                    help="planning-section fleet size (exact OG is "
                         "O(M^2) segments — keep it measurable, not "
                         "painful)")
    ap.add_argument("--cohort-size", type=int, default=48)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--arrival-seed", type=int, default=None,
                    help="deterministic seed for the Poisson arrival "
                         "draws alone (default: --seed)")
    ap.add_argument("--json", default="BENCH_scale.json",
                    help="machine-readable output path ('' disables)")
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny axes for CI (wiring + gate only)")
    args = ap.parse_args(argv)
    arrival_seed = args.seed if args.arrival_seed is None else \
        args.arrival_seed
    if args.dry_run:
        # never clobber the committed baseline snapshot with a tiny doc
        if args.json == ap.get_default("json"):
            args.json = "BENCH_scale_dryrun.json"
        if args.fleet_sizes == ap.get_default("fleet_sizes"):
            args.fleet_sizes = [200]
        if args.planning_users == ap.get_default("planning_users"):
            args.planning_users = 24
        if args.cohort_size == ap.get_default("cohort_size"):
            args.cohort_size = 8

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    print(f"{'M':>7} {'rate/s':>8} {'flushes':>7} {'batch μ/max':>11} "
          f"{'viol':>6} {'goodput/s':>9} {'J/req':>8} {'p50/p99 ms':>12} "
          f"{'wall':>7}")
    online, pipelined, traced = [], [], []
    for M in args.fleet_sizes:
        r, res = run_online_scale(M, args.load, args.seed, arrival_seed,
                                  policy=args.policy,
                                  batch_window=args.batch_window)
        online.append(r)
        lat = r["plan_latency"]
        print(f"{M:>7} {r['rate_hz']:>8.0f} {r['n_flushes']:>7} "
              f"{r['mean_batch']:>5.1f}/{r['max_batch']:<5} "
              f"{r['violations']:>6} {r['goodput_rps']:>9.0f} "
              f"{r['energy_per_request']:>8.5f} "
              f"{lat['p50_ms']:>5.1f}/{lat['p99_ms']:<6.1f} "
              f"{r['wall_s']:>6.1f}s")
        if args.plan_workers > 0:
            rp, resp = run_online_scale(M, args.load, args.seed,
                                        arrival_seed, policy=args.policy,
                                        batch_window=args.batch_window,
                                        plan_workers=args.plan_workers,
                                        plan_depth=args.plan_depth)
            rp["parity"] = _same_result(res, resp)
            rp["pipeline_speedup"] = (r["wall_s"] / rp["wall_s"]
                                      if rp["wall_s"] > 0 else 0.0)
            pipelined.append(rp)
            hits, misses = rp["plan_ahead_hits"], rp["plan_ahead_misses"]
            hit_rate = hits / (hits + misses) if hits + misses else 0.0
            print(f"{'':>7} pipelined x{args.plan_workers} "
                  f"d{args.plan_depth}: wall {rp['wall_s']:.1f}s "
                  f"({rp['pipeline_speedup']:.2f}x), plan-ahead "
                  f"{hits}/{hits + misses} hit ({hit_rate:.0%}), "
                  f"parity={'ok' if rp['parity'] else 'BROKEN'}")
        # traced twin: same run with the full telemetry stack on (tracer,
        # metrics, per-request records).  Sim results MUST be bitwise
        # identical (observers never perturb); the wall-time ratio is the
        # tracing overhead the nightly regression gate bounds.
        from repro.core import Telemetry, validate_events
        tel = Telemetry()
        rt, rest = run_online_scale(M, args.load, args.seed, arrival_seed,
                                    policy=args.policy,
                                    batch_window=args.batch_window,
                                    telemetry=tel)
        overhead = (rt["wall_s"] / r["wall_s"] - 1.0
                    if r["wall_s"] > 0 else 0.0)
        traced.append(dict(
            users=M, wall_s=rt["wall_s"],
            goodput_rps=rt["goodput_rps"],
            energy_per_request=rt["energy_per_request"],
            parity=_same_result(res, rest),
            trace_overhead=overhead,
            trace_events=len(tel.tracer.events),
            trace_clean=not validate_events(tel.tracer.events)))
        t = traced[-1]
        print(f"{'':>7} traced: wall {t['wall_s']:.1f}s "
              f"({100 * t['trace_overhead']:+.1f}%), "
              f"{t['trace_events']} event(s), "
              f"parity={'ok' if t['parity'] else 'BROKEN'}, "
              f"schema={'ok' if t['trace_clean'] else 'BROKEN'}")

    dynamic = None
    if args.plan_workers > 0:
        dynamic = run_dynamic_channel(min(10_000, max(args.fleet_sizes)),
                                      args.load, args.seed, arrival_seed,
                                      args.policy, args.batch_window,
                                      args.plan_workers, args.plan_depth)
        rdp = dynamic["pipelined"]
        h, ms = rdp["plan_ahead_hits"], rdp["plan_ahead_misses"]
        print(f"\ndynamic channel (shared uplink) at M={rdp['users']}: "
              f"sync {dynamic['sync']['wall_s']:.1f}s, pipelined "
              f"x{args.plan_workers} d{args.plan_depth} "
              f"{rdp['wall_s']:.1f}s ({rdp['pipeline_speedup']:.2f}x), "
              f"plan-ahead {h}/{h + ms} hit, "
              f"parity={'ok' if rdp['parity'] else 'BROKEN'}")

    p = run_planning_scale(args.planning_users, args.cohort_size, args.seed)
    print(f"\nplanning at M={p['users']} (cohort C={p['cohort_size']}):")
    print(f"  prefix OG     {p['exact_s']:>8.2f}s  E={p['exact_energy']:.4f}")
    print(f"  pareto OG     {p['pareto_s']:>8.2f}s  "
          f"E={p['pareto_energy']:.4f}  "
          f"vs prefix {100 * p['pareto_vs_prefix']:+.2f}%  "
          f"(frontier max {p['frontier_max']}, "
          f"{p['dominance_pruned']} pruned)")
    print(f"  adaptive OG   {p['adaptive_s']:>8.2f}s  "
          f"E={p['adaptive_energy']:.4f}  "
          f"win frac {p['adaptive_win_frac']:.2f}  "
          f"wall {p['adaptive_vs_pareto_wall']:.2f}x pareto / "
          f"{p['adaptive_vs_prefix_wall']:.2f}x prefix  "
          f"({p['beam_widenings']} widenings)")
    print(f"  cohort OG     {p['cohort_s']:>8.2f}s  "
          f"E={p['cohort_energy']:.4f}  "
          f"band {100 * p['cohort_energy_band']:+.2f}% vs prefix, "
          f"{100 * p['cohort_energy_band_vs_pareto']:+.2f}% vs pareto  "
          f"speedup {p['cohort_speedup']:.1f}x")
    print(f"  incremental   seed {p['incremental_seed_s']:.2f}s, "
          f"tail arrive {p['arrive_s']:.3f}s "
          f"({p['arrive_refold_levels']} level(s) re-folded, "
          f"{p['arrive_speedup']:.0f}x vs {p['scratch_s']:.2f}s scratch), "
          f"mid depart {p['depart_s']:.2f}s "
          f"({p['depart_refold_levels']} levels)")
    print(f"  pareto churn  seed {p['pareto_churn_seed_s']:.2f}s, "
          f"repeat {1e3 * p['pareto_churn_repeat_s']:.2f}ms "
          f"({'memoized' if p['pareto_churn_repeat_memoized'] else 'NOT MEMOIZED'}), "
          f"tail arrive {p['pareto_arrive_s']:.3f}s "
          f"({p['pareto_arrive_refold_levels']} level(s)), "
          f"mid depart {p['pareto_depart_s']:.2f}s, "
          f"parity={'ok' if p['pareto_churn_parity'] else 'BROKEN'}")

    # internal acceptance: every online run healthy, every pipelined run
    # bitwise-identical to its synchronous twin, every traced run
    # bitwise-identical AND schema-clean, the pareto DP sound
    # (<= prefix, and the cohort chain banded ONE-SIDED against it), the
    # prefix cohort band tight, the tail arrival actually incremental —
    # one level re-folded and measurably faster than scratch (its single
    # level still batch-solves M segments, so wall time shrinks less than
    # the level count does) (dry-run: wiring only)
    dyn_checks = 3 if dynamic is not None else 0
    total = len(online) + len(pipelined) + 2 * len(traced) + dyn_checks + 10
    wins = (sum(r["healthy"] for r in online)
            + sum(r["parity"] for r in pipelined)
            + sum(r["parity"] for r in traced)
            + sum(r["trace_clean"] for r in traced)
            + int(p["pareto_sound"])
            + int(p["adaptive_sound"])
            + int(p["adaptive_win_frac"] >= 0.9)
            + int(p["adaptive_vs_pareto_wall"] <= 1.1)
            + int(-1e-9 <= p["cohort_energy_band_vs_pareto"] <= 0.08)
            + int(abs(p["cohort_energy_band"]) <= 0.08)
            + int(p["tail_arrival_cheap"] and p["arrive_speedup"] > 1.3)
            + int(p["incremental_parity"])
            + int(p["pareto_churn_repeat_memoized"])
            + int(p["pareto_churn_parity"]))
    if dynamic is not None:
        wins += (int(dynamic["pipelined"]["parity"])
                 + int(dynamic["pipelined"]["plan_ahead_hits"] > 0)
                 + int(dynamic["pipelined"]["pipeline_speedup"] > 1.0))
    need = 1 if args.dry_run else total
    print(f"scale acceptance: {wins}/{total} checks pass "
          f"(gate: >= {need})")
    if args.json:
        doc = dict(benchmark="scale_bench",
                   mode="dry-run" if args.dry_run else "full",
                   python=platform.python_version(),
                   platform=platform.platform(),
                   jax_platforms=os.environ.get("JAX_PLATFORMS", ""),
                   load_per_user_hz=args.load, policy=args.policy,
                   plan_workers=args.plan_workers,
                   plan_depth=args.plan_depth,
                   gate_wins=wins, gate_needed=need,
                   online=online, pipelined=pipelined, traced=traced,
                   dynamic=dynamic, planning=p)
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"wrote {args.json} ({len(online)} online scales)")
    if wins < need:
        print("scale acceptance gate FAILED", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
