"""Co-inference serving driver: the paper's system end to end.

Builds a model (reduced by default, ``--full-width`` for the published
widths), an M-user fleet with deadlines, runs the J-DOB
scheduler, executes the partitioned/batched plan on the real model, and
verifies outputs equal the monolithic forward.

  PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --users 6

``--online`` switches to the event-driven path: requests arrive as a
Poisson stream and the server's :class:`~repro.core.OnlineScheduler`
batches them under a flush policy, executing each flush on the model the
moment it is booked (GPU occupancy threaded between flushes):

  PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --users 6 \\
      --online --rate 100 --policy slack

``--tenants N`` runs the multi-tenant regime: N independent Poisson
streams with distinct task profiles (per-tenant sequence lengths →
different block workloads) and deadlines, arbitrated over ONE shared GPU
by the tenancy subsystem (queued-batch preemption + admission control),
each tenant's flushes executing on its own model:

  PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --users 4 \\
      --tenants 3 --rate 200 --admission degrade

``--channel {static,shared,trace}`` picks the uplink model (shared-medium
contention / Markov fading; ``--channel-nominal`` plans at solo rates on a
contended channel — the bench baseline); the report then includes the
realized-vs-planned upload error and actualization replan counts.

``--trace out.json`` records the whole run as a Perfetto-loadable Chrome
trace (simulation-time tracks for each tenant, the GPU, the uplink and the
planner); ``--metrics-json out.json`` dumps the metrics registry +
per-request lifecycle records.  Both observe without perturbing: results
are bit-identical with telemetry on or off.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS
from repro.core import (PlannerService, Telemetry, local_computing,
                        make_channel, make_edge_profile, make_fleet,
                        profile_from_arch)
from repro.core.telemetry import TID_RUN
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.serving import (CoInferenceServer, MultiTenantServer, Request,
                           TenantModel)


def _build_model(args, seed: int):
    """The served model: the registered architecture at its published
    widths and depth with bf16 weights (``--full-width``), or its reduced
    smoke variant with float32 weights."""
    key = jax.random.PRNGKey(seed)
    if args.full_width:
        cfg = ARCHS[args.arch]
        params = jax.jit(lambda k: init_params(cfg, k, jnp.bfloat16))(key)
    else:
        cfg = ARCHS[args.arch].reduced()
        params = init_params(cfg, key)
    return cfg, params


def _build_channel(args):
    """The uplink model behind ``--channel`` (None = seed-path static)."""
    if args.channel == "static":
        return None
    return make_channel(args.channel, share=args.channel_share,
                        seed=args.seed)


#: co-inference vs monolithic logits, relative to max |logit|.  Both sides
#: run the same float32 layer steps, only at different batch sizes, and
#: XLA may tile a contraction differently per batch shape, which reorders
#: float32 sums.  Measured: 7.1e-7 at minitron-4b widths on a TPU v5e, up
#: to 7e-7 on the CPU at reduced widths; one bf16 pass per matmul (the
#: v5e's default precision) gives 5.4e-3, which this bound rejects.
LOGIT_RTOL = 1e-5


def _verify(report_logits, executor, reqs, rows=slice(None)) -> float:
    """max |Δlogit| of the served rows against the monolithic forward;
    raises past :data:`LOGIT_RTOL` of the logits' scale."""
    want = np.asarray(executor.full_forward(
        jnp.asarray(np.stack([r.tokens for r in reqs]))))[rows]
    err = float(np.abs(report_logits[rows] - want).max())
    scale = float(np.abs(want).max())
    print(f"co-inference vs monolithic max |Δlogit| = {err:.2e} "
          f"(max |logit| {scale:.2e}, bound {LOGIT_RTOL:.0e} of it)")
    if not err <= LOGIT_RTOL * scale:
        raise RuntimeError(f"co-inference logits differ from the "
                           f"monolithic forward by {err:.3e}")
    return err


def _plan_latency_line(service) -> None:
    stats = service.stats()
    lat = stats.plan_latency()
    if lat["count"]:
        print(f"plan latency: {lat['count']} dispatch(es), "
              f"min {lat['min_ms']:.2f} ms / p50 {lat['p50_ms']:.2f} ms / "
              f"p99 {lat['p99_ms']:.2f} ms / max {lat['max_ms']:.2f} ms "
              f"(steady-state)")
        comp = lat["compile"]
        if comp["count"]:
            print(f"  cold compiles: {comp['count']} sample(s), "
                  f"p50 {comp['p50_ms']:.1f} ms / max {comp['max_ms']:.1f} ms "
                  f"(separate bucket)")
    if stats.frontier_states:
        beam = (f", beam widened {stats.beam_widenings}x"
                if stats.beam_widenings else "")
        print(f"pareto DP: {stats.frontier_states} frontier state(s) "
              f"(max {stats.frontier_max}/level), "
              f"{stats.dominance_pruned} dominance-pruned{beam}")
    if stats.plan_ahead_hits or stats.plan_ahead_misses:
        total = stats.plan_ahead_hits + stats.plan_ahead_misses
        print(f"plan-ahead: {stats.plan_ahead_hits}/{total} speculative "
              f"plan(s) consumed")
    if stats.og_plans:
        print(f"grouping DP: {stats.og_plans} plan(s), "
              f"{stats.dispatches_per_plan:.1f} dispatch(es)/plan")
    fused = stats.fused_scan_latency()
    if fused["count"] or fused["fallbacks"] or fused["routed"]:
        print(f"fused DP scans: {fused['count']} scan(s), "
              f"p50 {fused['p50_ms']:.2f} ms / max {fused['max_ms']:.2f} ms "
              f"wall, {fused['compiles']} compile(s), "
              f"{fused['fallbacks']} fallback(s), "
              f"{fused['routed']} size-routed to dispatch")


def _begin_run(telemetry) -> None:
    """Open the run-level ``serve`` B/E pair on the run track (closed by
    :func:`_finish_telemetry` at the simulated end of service)."""
    if telemetry is None:
        return
    telemetry.tracer.name_track(TID_RUN, "run")
    telemetry.tracer.begin("serve", 0.0, TID_RUN)


def _finish_telemetry(telemetry, args, service, end_t: float) -> None:
    """Close the run span and write ``--trace`` / ``--metrics-json``."""
    if telemetry is None:
        return
    telemetry.tracer.end("serve", max(0.0, end_t), TID_RUN)
    if args.trace:
        telemetry.export_trace(args.trace)
        print(f"trace: {len(telemetry.tracer.events)} event(s) -> "
              f"{args.trace} (chrome://tracing / ui.perfetto.dev)")
    if args.metrics_json:
        telemetry.export_metrics(args.metrics_json,
                                 planner_stats=service.stats())
        print(f"metrics -> {args.metrics_json}")


def _serve_offline(server, fleet, profile, edge, reqs, args,
                   telemetry=None) -> dict:
    _begin_run(telemetry)
    t0 = time.perf_counter()
    report = server.serve(reqs, cohort_size=args.cohort_size,
                          planner=args.planner, beam_width=args.beam_width,
                          dp_backend=args.dp_backend, telemetry=telemetry)
    serve_s = time.perf_counter() - t0
    lc = local_computing(profile, fleet, edge)
    print(f"arch={server.cfg.name}  M={args.users}  N={profile.N} blocks  "
          f"planner={args.planner}  dp_backend={args.dp_backend}  "
          f"(planned+served in {serve_s:.2f}s via planner service)")
    for g, s in zip(report.groups, report.schedules):
        print(f"  group {list(g)}: partition ñ={s.partition}, "
              f"batch={s.batch_size}, f_e={s.f_edge / 1e9:.2f} GHz, "
              f"energy={s.energy:.4f} J")
    print(f"total energy: {report.energy:.4f} J "
          f"(LC: {lc.energy:.4f} J, saving "
          f"{100 * (1 - report.energy / lc.energy):.1f}%)")
    err = _verify(report.logits, server.executor, reqs)
    _plan_latency_line(server.service)
    _finish_telemetry(telemetry, args, server.service, report.t_free_end)
    return dict(energy=report.energy, lc=lc.energy, err=err,
                partitions=report.partitions, batches=report.batch_sizes)


def _serve_online(server, fleet, profile, edge, reqs, args,
                  telemetry=None) -> dict:
    _begin_run(telemetry)
    t0 = time.perf_counter()
    report = server.serve_online(reqs, policy=args.policy,
                                 window=args.window,
                                 occupancy=args.occupancy,
                                 channel=_build_channel(args),
                                 channel_aware=not args.channel_nominal,
                                 channel_stagger=args.channel_stagger,
                                 batch_window=args.batch_window,
                                 batch_events=args.batch_events,
                                 plan_workers=args.plan_workers,
                                 plan_depth=args.plan_depth,
                                 telemetry=telemetry)
    serve_s = time.perf_counter() - t0
    lc = local_computing(profile, fleet, edge)
    print(f"arch={server.cfg.name}  M={args.users}  N={profile.N} blocks  "
          f"online policy={args.policy}  rate={args.rate}/s  "
          f"occupancy={args.occupancy}  "
          f"(planned+served in {serve_s:.2f}s, event-driven)")
    for ev in report.flushes:
        f_e = (f"{ev.schedule.f_edge / 1e9:.2f} GHz"
               if ev.schedule.offload.any() else "local")
        print(f"  t={ev.time * 1e3:8.2f} ms  flush users={list(ev.users)}  "
              f"ñ={ev.schedule.partition}  batch={ev.schedule.batch_size}  "
              f"f_e={f_e}  "
              f"energy={ev.schedule.energy:.4f} J  "
              f"gpu_free={ev.gpu_free * 1e3:.2f} ms")
    print(f"total energy: {report.energy:.4f} J (LC: {lc.energy:.4f} J)  "
          f"violations={report.violations}  "
          f"gpu busy until {report.gpu_busy_until * 1e3:.2f} ms")
    if args.occupancy == "interleaved":
        print(f"timeline: {report.gap_fills} gap-fill(s), "
              f"{report.dvfs_rescales} per-flush DVFS rescale(s) saving "
              f"{report.dvfs_energy_saved:.4f} J, "
              f"{report.pruned_probes} gap probe(s) pruned")
    if report.channel != "static":
        print(f"channel={report.channel}: realized-vs-planned upload error "
              f"Σ|Δ| = {report.upload_error * 1e3:.2f} ms, "
              f"{report.channel_replans} actualization replan(s), "
              f"{report.realized_late} realized-late request(s), "
              f"{report.stagger_replans} stagger re-price(s)")
    err = _verify(report.logits, server.executor, reqs)
    if report.violations:
        # legitimate under tight --beta: requests past their point of no
        # return by the time the policy flushed — report, don't crash
        print(f"WARNING: {report.violations} deadline violation(s) — "
              f"tighten the policy (--policy immediate) or relax --beta")
    stats = server.service.stats()
    print(f"planner service: {stats.dispatches} dispatches, "
          f"{stats.hits} cache hits / {stats.misses} compiles / "
          f"{stats.evictions} evictions")
    _plan_latency_line(server.service)
    _finish_telemetry(telemetry, args, server.service,
                      report.gpu_busy_until)
    return dict(energy=report.energy, lc=lc.energy, err=err,
                violations=report.violations,
                n_flushes=len(report.flushes),
                partitions=[ev.schedule.partition for ev in report.flushes],
                batches=[ev.schedule.batch_size for ev in report.flushes])


def _serve_tenants(args, telemetry=None) -> dict:
    """N tenants with distinct profiles/deadlines on one shared GPU."""
    rng = np.random.default_rng(args.seed)
    arr_rng = (rng if args.arrival_seed is None
               else np.random.default_rng(args.arrival_seed))
    models, streams = [], []
    for t in range(args.tenants):
        cfg, params = _build_model(args, args.seed + t)
        seq = args.seq + 8 * t                   # distinct task profiles
        profile = profile_from_arch(cfg, seq=seq)
        edge = make_edge_profile(profile)
        beta = (args.beta[0] * (1.0 + 0.5 * t), args.beta[1] * (1.0 + 0.5 * t))
        fleet = make_fleet(args.users, profile, edge, beta=beta,
                           seed=args.seed + t)
        models.append(TenantModel(f"tenant{t}", cfg, params, profile, fleet,
                                  edge, policy=args.policy,
                                  window=args.window))
        arr = np.cumsum(arr_rng.exponential(1.0 / args.rate, args.users))
        streams.append([Request(user=m,
                                tokens=rng.integers(0, cfg.vocab_size, seq,
                                                    dtype=np.int32),
                                deadline=float(fleet.deadline[m]),
                                arrival=float(arr[m]))
                        for m in range(args.users)])

    service = PlannerService(models[0].profile, models[0].edge,
                             default_dp_backend=args.dp_backend)
    server = MultiTenantServer(models, service=service,
                               preemption=not args.no_preemption,
                               admission=args.admission,
                               occupancy=args.occupancy,
                               channel=_build_channel(args),
                               channel_aware=not args.channel_nominal,
                               channel_stagger=args.channel_stagger,
                               batch_window=args.batch_window,
                               plan_workers=args.plan_workers,
                               plan_depth=args.plan_depth,
                               telemetry=telemetry)
    _begin_run(telemetry)
    t0 = time.perf_counter()
    report = server.serve_online(streams, batch_events=args.batch_events)
    serve_s = time.perf_counter() - t0
    print(f"arch={args.arch}  tenants={args.tenants}  M={args.users}/tenant  "
          f"policy={args.policy}  admission={args.admission}  "
          f"occupancy={args.occupancy}  channel={args.channel}  "
          f"(planned+served in {serve_s:.2f}s, shared-GPU arbitration)")
    max_err = 0.0
    for tid, (m, reqs, tr) in enumerate(zip(models, streams,
                                            report.result.tenants)):
        mask = report.served[tid]
        f_es = [f"{f / 1e9:.2f}" if f is not None else "loc"
                for f in tr.result.f_edges]
        print(f"  {tr.name}: seq={len(reqs[0].tokens)}  "
              f"energy={tr.energy:.4f} J  flushes={tr.result.n_flushes}  "
              f"batches={tr.result.batch_sizes}  f_e/GHz={f_es}  "
              f"late={tr.result.violations}"
              f"  degraded={tr.degraded}  rejected={tr.rejected}  "
              f"tax +{tr.preempt_tax_inflicted:.4f}/-"
              f"{tr.preempt_tax_suffered:.4f} J")
        if mask.any():
            max_err = max(max_err, _verify(report.logits[tid],
                                           server.executors[tid], reqs,
                                           rows=mask))
    print(f"total energy: {report.energy:.4f} J  "
          f"violations={report.violations}  "
          f"preemptions={report.preemptions}  "
          f"gpu busy until {report.gpu_busy_until * 1e3:.2f} ms")
    if args.occupancy == "interleaved":
        res = report.result
        print(f"timeline: {res.gap_fills} gap-fill(s), "
              f"{res.dvfs_rescales} per-flush DVFS rescale(s) saving "
              f"{res.dvfs_energy_saved:.4f} J, "
              f"{res.pruned_probes} gap probe(s) pruned  "
              f"(what-if trial reuse {res.replan_trial_hits}/"
              f"{res.replan_trial_hits + res.replan_trial_misses})")
    if report.result.channel != "static":
        res = report.result
        print(f"channel={res.channel}: realized-vs-planned upload error "
              f"Σ|Δ| = {res.upload_error * 1e3:.2f} ms, "
              f"{res.channel_replans} actualization replan(s), "
              f"{res.realized_late} realized-late request(s), "
              f"{res.stagger_replans} stagger re-price(s)")
    stats = server.service.stats()
    print(f"planner service family: {stats.dispatches} dispatches, "
          f"{stats.hits} cache hits / {stats.misses} compiles")
    _plan_latency_line(server.service)
    _finish_telemetry(telemetry, args, server.service,
                      report.gpu_busy_until)
    return dict(energy=report.energy, violations=report.violations,
                preemptions=report.preemptions, err=max_err,
                tenants=args.tenants)


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--full-width", action="store_true",
                    help="serve the architecture at its published widths "
                         "and depth with bf16 weights (default: its "
                         "reduced smoke variant, float32 weights)")
    ap.add_argument("--users", type=int, default=6)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--beta", type=float, nargs=2, default=[2.0, 8.0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--arrival-seed", type=int, default=None,
                    help="deterministic seed for the Poisson arrival draws "
                         "alone (default: --seed) — lets load traces vary "
                         "while weights/tokens stay pinned, and vice versa")
    ap.add_argument("--online", action="store_true",
                    help="event-driven serving over a Poisson arrival stream")
    ap.add_argument("--rate", type=float, default=100.0,
                    help="online arrival rate (requests/s)")
    ap.add_argument("--policy", default="slack",
                    choices=["immediate", "window", "slack", "lastcall"])
    ap.add_argument("--window", type=float, default=0.02)
    ap.add_argument("--cohort-size", type=int, default=None,
                    help="hierarchical planning threshold: fleets larger "
                         "than this split into deadline-sorted cohorts "
                         "merged by a boundary DP (offline serving; "
                         "None = always-exact OG)")
    ap.add_argument("--planner", default="prefix",
                    choices=["prefix", "pareto"],
                    help="grouping DP: prefix = the seed's one-state-per-"
                         "prefix recurrence; pareto = frontier of "
                         "(energy, cursor) states — sound under occupancy "
                         "coupling, never above prefix (offline serving)")
    ap.add_argument("--plan-workers", type=int, default=0,
                    help="plan-ahead workers for --batch-events: overlap "
                         "the next flush's speculative solve with the "
                         "current batch (0 = synchronous; results are "
                         "bit-identical at any count)")
    ap.add_argument("--plan-depth", type=int, default=1,
                    help="speculation chain depth for --plan-workers: "
                         "plan this many drained flushes ahead by chaining "
                         "the predicted occupancy cursor (bit-identical at "
                         "any depth)")
    ap.add_argument("--dp-backend", default="dispatch",
                    choices=["dispatch", "fused"],
                    help="grouping-DP fold: dispatch = host level loop "
                         "(one device launch per level); fused = the "
                         "whole DP as one jitted device scan — "
                         "bit-identical plans, O(1) dispatches per plan "
                         "(becomes the planner service default, so "
                         "online/tenant flush plans fold fused too)")
    ap.add_argument("--beam-width", default=None,
                    type=lambda v: v if v == "auto" else int(v),
                    help="pareto-DP frontier cap (offline serving): an int "
                         "hard-caps each level, 'auto' self-sizes from 1 — "
                         "widening only at levels that fork — while never "
                         "exceeding the prefix DP's energy")
    ap.add_argument("--batch-events", action="store_true",
                    help="drain the event queue through the fleet-scale "
                         "batched loop (bit-identical at "
                         "--batch-window 0)")
    ap.add_argument("--batch-window", type=float, default=0.0,
                    help="epsilon batching window (s) for --batch-events: "
                         "arrivals this close to the policy flush time "
                         "join the same drain pass")
    ap.add_argument("--channel-stagger", action="store_true",
                    help="re-price each flush against staggered upload "
                         "starts (devices finish local blocks at "
                         "different times) instead of the all-concurrent "
                         "contention snapshot")
    ap.add_argument("--tenants", type=int, default=1,
                    help="co-resident models sharing the GPU (>1 switches "
                         "to the tenancy subsystem)")
    ap.add_argument("--admission", default="admit",
                    choices=["admit", "degrade", "reject"])
    ap.add_argument("--no-preemption", action="store_true",
                    help="disable queued-batch preemption (tenants>1)")
    ap.add_argument("--occupancy", default="serialized",
                    choices=["serialized", "interleaved"],
                    help="GPU timeline mode: serialized = the paper's "
                         "scalar Eq. 22 horizon; interleaved = gap-fill "
                         "small batches into idle windows + per-flush "
                         "edge DVFS against reservation slack")
    ap.add_argument("--channel", default="static",
                    choices=["static", "shared", "trace"],
                    help="uplink model: static = the paper's frozen "
                         "Shannon scalars; shared = concurrent uploads "
                         "split the medium; trace = Markov good/bad "
                         "fading traces")
    ap.add_argument("--channel-share", default="equal",
                    choices=["equal", "weighted"],
                    help="shared-uplink split: equal slots or "
                         "bandwidth-weighted")
    ap.add_argument("--channel-nominal", action="store_true",
                    help="plan at the nominal solo rates even on a "
                         "contended channel (the baseline the channel "
                         "bench measures channel-aware planning against)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON timeline of the "
                         "run (load in chrome://tracing or "
                         "ui.perfetto.dev): one track per tenant plus "
                         "GPU / uplink / planner tracks, all timestamps "
                         "in SIMULATION time; enabling tracing never "
                         "changes results (tested bit-identical)")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the telemetry metrics registry (counters, "
                         "gauges, latency digests), per-request lifecycle "
                         "records and planner stats as JSON; the only "
                         "wall-clock numbers are under the explicitly "
                         "labeled 'wall_time' section")
    args = ap.parse_args(argv)
    enable_compile_cache()

    telemetry = (Telemetry() if args.trace or args.metrics_json else None)
    if args.tenants > 1:
        return _serve_tenants(args, telemetry)

    cfg, params = _build_model(args, args.seed)
    profile = profile_from_arch(cfg, seq=args.seq)
    edge = make_edge_profile(profile)
    fleet = make_fleet(args.users, profile, edge, beta=tuple(args.beta),
                       seed=args.seed)
    server = CoInferenceServer(
        cfg, params, profile, fleet, edge,
        service=PlannerService(profile, edge,
                               default_dp_backend=args.dp_backend))

    rng = np.random.default_rng(args.seed)
    # a distinct --arrival-seed re-rolls the load trace only; the default
    # shares the stream (byte-stable with previous releases)
    arr_rng = (rng if args.arrival_seed is None
               else np.random.default_rng(args.arrival_seed))
    arrivals = (np.cumsum(arr_rng.exponential(1.0 / args.rate, args.users))
                if args.online else np.zeros(args.users))
    reqs = [Request(user=m,
                    tokens=rng.integers(0, cfg.vocab_size, args.seq,
                                        dtype=np.int32),
                    deadline=float(fleet.deadline[m]),
                    arrival=float(arrivals[m]))
            for m in range(args.users)]

    if args.online:
        return _serve_online(server, fleet, profile, edge, reqs, args,
                             telemetry)
    if args.occupancy != "serialized":
        # the one-shot OG path threads the serialized DP cursor only
        # (ROADMAP timeline follow-up d) — don't let the flag silently
        # imply interleaved numbers
        print("NOTE: --occupancy interleaved applies to --online/--tenants "
              "serving; offline OG serving is serialized-only")
    if args.channel != "static":
        # the one-shot OG wave has no arrival process to contend over —
        # realized channel divergence is an online phenomenon
        print("NOTE: --channel applies to --online/--tenants serving; "
              "offline OG serving prices the static solo rates")
    return _serve_offline(server, fleet, profile, edge, reqs, args,
                          telemetry)


if __name__ == "__main__":
    main()
