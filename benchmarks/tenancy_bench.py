"""Multi-tenant arbitration benchmark: shared-GPU scheduling quality.

For 2/4/8 co-resident tenants (MobileNetV2 variants at distinct input
resolutions → distinct task profiles, each with its own Poisson fleet and
deadlines), compares:

* **arbitrated** — the tenancy subsystem: per-tenant slack batching, one
  shared occupancy timeline (Eq. 22 global in serialized mode),
  queued-batch preemption and degrade-to-local admission control.
* **naive FIFO** — per-tenant FIFO sharing: every arrival flushes
  immediately and batches merely queue on the GPU in arrival order (no
  arbitration, no preemption, no admission control).
* **oracle** — sum of per-tenant clairvoyant bounds with an EXCLUSIVE GPU
  each: a lower bound no shared-GPU schedule can beat.

The acceptance gate (exit non-zero on failure) requires the arbitrated
scheduler to beat naive FIFO on total energy at an equal-or-lower
violation rate in at least 2 of the 3 scenarios.  Results are written as
machine-readable JSON (``BENCH_tenancy.json``) so the trajectory is
tracked across PRs; per-tenant preemption-tax fairness (energy inflicted
vs suffered through preemption re-plans) rides along in each record.

A second scenario set exercises the **GPU timeline occupancy modes**
(``BENCH_timeline.json``): heterogeneous-device fleets (α ∈ [0.5, 3] —
slow phones next to fast ones, the regime where upload-delayed GPU starts
leave real idle windows) are run under ``serialized`` (the paper's scalar
Eq. 22 horizon) and ``interleaved`` (gap-filling + per-flush edge DVFS)
occupancy.  Its gate requires interleaved to save energy at
equal-or-fewer violations in at least 2 of the 3 scenarios.

A third scenario set exercises the **wireless channel subsystem**
(``BENCH_channel.json``): shared-uplink contention (equal and
bandwidth-weighted splits) and Markov good/bad fading, comparing J-DOB
with channel-aware planning (flush plans price the contended-rate
snapshot) against planning at nominal solo rates — both realized on the
SAME channel, so the nominal runs pay through the actualization pass
(realized upload energy, forced edge speed-ups, bounded re-plans,
realized deadline slips).  Its gate requires channel-aware planning to
save energy at equal-or-fewer violations in at least 2 of the 3
contention/fading scenarios.

  PYTHONPATH=src python benchmarks/tenancy_bench.py            # T = 2/4/8
  PYTHONPATH=src python benchmarks/tenancy_bench.py --dry-run  # CI smoke
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time

RESOLUTIONS = (224, 192, 160, 128)


def build_scenario(n_tenants: int, users: int, rate: float, seed: int,
                   alpha=1.0, beta_scale: float = 1.0,
                   bw_spread: float = 1.0):
    from repro.core import (Tenant, make_edge_profile, make_fleet,
                            mobilenet_v2_profile, poisson_arrivals)
    tenants, traces = [], []
    for k in range(n_tenants):
        profile = mobilenet_v2_profile(
            input_res=RESOLUTIONS[k % len(RESOLUTIONS)])
        edge = make_edge_profile(profile)
        beta = (beta_scale * (6.0 + 2.0 * (k % 3)),
                beta_scale * (18.0 + 4.0 * (k % 3)))
        # per-tenant uplink bandwidth asymmetry (bw_spread > 1): tenant 0
        # keeps the Table-I 10 MHz, the last gets bw_spread x that — the
        # regime where a weighted shared uplink differs from equal slots
        bw = 10e6 * (1.0 + (bw_spread - 1.0) * k / max(1, n_tenants - 1))
        fleet = make_fleet(users, profile, edge, beta=beta, seed=seed + k,
                           alpha=alpha, bandwidth_hz=bw)
        tenants.append(Tenant(profile, fleet, edge,
                              name=f"mnv2@{RESOLUTIONS[k % 4]}#{k}"))
        traces.append(poisson_arrivals(users, rate, fleet,
                                       seed=seed + 100 + k))
    return tenants, traces


def run_scenario(n_tenants: int, users: int, rate: float, seed: int) -> dict:
    from repro.core import (MultiTenantScheduler, PlannerService, naive_fifo,
                            single_tenant_oracle)
    tenants, traces = build_scenario(n_tenants, users, rate, seed)
    service = PlannerService(tenants[0].profile, tenants[0].edge)

    t0 = time.perf_counter()
    mts = MultiTenantScheduler(tenants, service=service, preemption=True,
                               admission="degrade")
    mts.submit_traces(traces)
    arb = mts.run()
    t_arb = time.perf_counter() - t0

    t0 = time.perf_counter()
    fifo = naive_fifo(tenants, traces, service=service)
    t_fifo = time.perf_counter() - t0

    oracle = single_tenant_oracle(tenants, traces, service=service)
    stats = service.stats()
    n_req = arb.requests
    return dict(
        tenants=n_tenants, users_per_tenant=users, rate_hz=rate, seed=seed,
        requests=n_req,
        energy_arbitrated=arb.energy, energy_naive=fifo.energy,
        energy_oracle=oracle,
        violations_arbitrated=arb.violations, violations_naive=fifo.violations,
        violation_rate_arbitrated=arb.violations / n_req,
        violation_rate_naive=fifo.violations / n_req,
        preemptions=arb.preemptions, bookings=arb.bookings,
        degraded=sum(t.degraded for t in arb.tenants),
        rejected=sum(t.rejected for t in arb.tenants),
        flushes_arbitrated=sum(t.result.n_flushes for t in arb.tenants),
        flushes_naive=sum(t.result.n_flushes for t in fifo.tenants),
        wall_s_arbitrated=t_arb, wall_s_naive=t_fifo,
        planner_dispatches=stats.dispatches, planner_compiles=stats.misses,
        cached_shapes=service.cached_shapes,
        beats_naive=bool(arb.energy < fifo.energy
                         and arb.violations <= fifo.violations),
        saving_vs_naive=1.0 - arb.energy / fifo.energy,
        gap_vs_oracle=arb.energy / oracle - 1.0,
        replan_trial_hits=arb.replan_trial_hits,
        replan_trial_misses=arb.replan_trial_misses,
        # per-tenant preemption tax (ROADMAP follow-up d): J this tenant's
        # preemptions inflicted on others vs suffered from theirs
        preemption_tax=[dict(name=t.name,
                             inflicted=t.preempt_tax_inflicted,
                             suffered=t.preempt_tax_suffered)
                        for t in arb.tenants],
    )


def run_timeline_scenario(n_tenants: int, users: int, rate: float,
                          seed: int) -> dict:
    """Serialized vs interleaved occupancy on ONE shared PlannerService.
    Fleets are heterogeneous (α ∈ [0.5, 3]) so device compute + uplink
    delays the GPU start of big batches — the idle windows gap-filling
    exists to exploit."""
    from repro.core import MultiTenantScheduler, PlannerService
    tenants, traces = build_scenario(n_tenants, users, rate, seed,
                                     alpha=(0.5, 3.0))
    service = PlannerService(tenants[0].profile, tenants[0].edge)
    out = {}
    walls = {}
    for occ in ("serialized", "interleaved"):
        t0 = time.perf_counter()
        mts = MultiTenantScheduler(tenants, service=service, preemption=True,
                                   admission="degrade", occupancy=occ)
        mts.submit_traces(traces)
        out[occ] = mts.run()
        walls[occ] = time.perf_counter() - t0
    ser, inter = out["serialized"], out["interleaved"]
    return dict(
        tenants=n_tenants, users_per_tenant=users, rate_hz=rate, seed=seed,
        alpha=[0.5, 3.0], requests=ser.requests,
        energy_serialized=ser.energy, energy_interleaved=inter.energy,
        violations_serialized=ser.violations,
        violations_interleaved=inter.violations,
        preemptions_serialized=ser.preemptions,
        preemptions_interleaved=inter.preemptions,
        gap_fills=inter.gap_fills, dvfs_rescales=inter.dvfs_rescales,
        dvfs_energy_saved=inter.dvfs_energy_saved,
        degraded_serialized=sum(t.degraded for t in ser.tenants),
        degraded_interleaved=sum(t.degraded for t in inter.tenants),
        scrubbed_interleaved=sum(t.scrubbed for t in inter.tenants),
        wall_s_serialized=walls["serialized"],
        wall_s_interleaved=walls["interleaved"],
        beats_serialized=bool(inter.energy < ser.energy
                              and inter.violations <= ser.violations),
        saving_vs_serialized=1.0 - inter.energy / ser.energy,
    )


#: the contention/fading scenario axis (BENCH_channel.json): J-DOB with
#: channel-aware planning vs planning at nominal (solo Shannon) rates,
#: both realized on the SAME wireless channel
CHANNEL_SCENARIOS = (
    dict(name="shared-equal-T2", kind="shared", share="equal",
         tenants=2, rate_scale=1.0),
    # per-tenant bandwidth asymmetry (tenant 3 subscribes 2x tenant 0's
    # bandwidth): the weighted split hands the wide-band devices more of
    # the contended medium, which only a channel-aware plan can price
    dict(name="shared-weighted-T4", kind="shared", share="weighted",
         tenants=4, rate_scale=1.0, bw_spread=2.0),
    # tighter deadlines (beta_scale): a fade the nominal planner ignores
    # must be absorbed by device/edge speed-ups, not by slack
    dict(name="fading-T2", kind="trace", bad_gain=0.2,
         tenants=2, rate_scale=0.5, beta_scale=0.5),
)


def run_channel_scenario(spec: dict, users: int, rate: float,
                         seed: int) -> dict:
    """Channel-aware planning vs nominal-rate planning under the SAME
    realized channel.  Both runs see identical tenants, traces and channel
    dynamics; only the rates the PLANNER prices differ — the aware run
    snapshots the contended/faded rate, the nominal run keeps the solo
    Shannon scalars and pays through the actualization pass (realized
    upload energy, forced edge speed-ups, bounded re-plans, realized
    deadline slips).  A third run ("stagger") plans channel-aware AND
    re-prices each flush against the staggered upload starts (devices
    finish their local blocks at different times, so the concurrent-
    contention snapshot over-shares the medium) — the tightening shows up
    as lower realized upload error at equal-or-fewer violations."""
    from repro.core import (MultiTenantScheduler, PlannerService,
                            make_channel)
    n_tenants = spec["tenants"]
    rate = rate * spec.get("rate_scale", 1.0)
    tenants, traces = build_scenario(n_tenants, users, rate, seed,
                                     beta_scale=spec.get("beta_scale", 1.0),
                                     bw_spread=spec.get("bw_spread", 1.0))
    service = PlannerService(tenants[0].profile, tenants[0].edge)
    out, walls = {}, {}
    for mode in ("aware", "nominal", "stagger"):
        channel = make_channel(spec["kind"], share=spec.get("share", "equal"),
                               bad_gain=spec.get("bad_gain", 0.25),
                               seed=seed)
        t0 = time.perf_counter()
        mts = MultiTenantScheduler(tenants, service=service, preemption=True,
                                   admission="degrade", channel=channel,
                                   channel_aware=(mode != "nominal"),
                                   channel_stagger=(mode == "stagger"))
        mts.submit_traces([list(tr) for tr in traces])
        out[mode] = mts.run()
        walls[mode] = time.perf_counter() - t0
    aware, nominal, stagger = out["aware"], out["nominal"], out["stagger"]
    return dict(
        scenario=spec["name"], kind=spec["kind"],
        share=spec.get("share"), tenants=n_tenants,
        users_per_tenant=users, rate_hz=rate, seed=seed,
        requests=aware.requests,
        energy_aware=aware.energy, energy_nominal=nominal.energy,
        violations_aware=aware.violations,
        violations_nominal=nominal.violations,
        upload_error_aware=aware.upload_error,
        upload_error_nominal=nominal.upload_error,
        channel_replans_aware=aware.channel_replans,
        channel_replans_nominal=nominal.channel_replans,
        realized_late_aware=aware.realized_late,
        realized_late_nominal=nominal.realized_late,
        degraded_aware=sum(t.degraded for t in aware.tenants),
        degraded_nominal=sum(t.degraded for t in nominal.tenants),
        wall_s_aware=walls["aware"], wall_s_nominal=walls["nominal"],
        energy_stagger=stagger.energy,
        violations_stagger=stagger.violations,
        upload_error_stagger=stagger.upload_error,
        stagger_replans=stagger.stagger_replans,
        wall_s_stagger=walls["stagger"],
        stagger_tightens=bool(
            stagger.upload_error <= aware.upload_error + 1e-12
            and stagger.violations <= aware.violations),
        beats_nominal=bool(aware.energy < nominal.energy
                           and aware.violations <= nominal.violations),
        saving_vs_nominal=1.0 - aware.energy / nominal.energy,
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", type=int, nargs="+", default=[2, 4, 8])
    ap.add_argument("--users", type=int, default=8,
                    help="fleet size per tenant")
    ap.add_argument("--rate", type=float, default=300.0,
                    help="per-tenant Poisson arrival rate (requests/s)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--json", default="BENCH_tenancy.json",
                    help="machine-readable output path ('' disables)")
    ap.add_argument("--timeline-json", default="BENCH_timeline.json",
                    help="occupancy-mode comparison output ('' disables "
                         "the timeline scenario set entirely)")
    ap.add_argument("--timeline-rate", type=float, default=1500.0,
                    help="per-tenant arrival rate for the timeline "
                         "scenarios (denser than the arbitration set: "
                         "idle-window interleaving needs contention)")
    ap.add_argument("--channel-json", default="BENCH_channel.json",
                    help="channel-aware vs nominal-rate planning "
                         "comparison output ('' disables the channel "
                         "scenario set entirely)")
    ap.add_argument("--channel-rate", type=float, default=900.0,
                    help="per-tenant arrival rate for the channel "
                         "scenarios (dense: shared-uplink contention "
                         "needs overlapping uploads)")
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny scenario set for CI (wiring + gate only)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.dry_run:
        # never clobber the committed baseline snapshots (the regression
        # gate's reference) with a tiny dry-run doc: divert default
        # output paths; explicit paths are honored as given
        if args.json == ap.get_default("json"):
            args.json = "BENCH_tenancy_dryrun.json"
        if args.timeline_json == ap.get_default("timeline_json"):
            args.timeline_json = "BENCH_timeline_dryrun.json"
        if args.channel_json == ap.get_default("channel_json"):
            args.channel_json = "BENCH_channel_dryrun.json"

    scenarios = [(2, 3)] if args.dry_run else [(t, args.users)
                                              for t in args.tenants]
    print(f"{'T':>3} {'M/t':>4} {'arbitrated':>11} {'naive FIFO':>11} "
          f"{'oracle':>9} {'saving':>7} {'viol a/n':>9} {'preempt':>7}")
    records = []
    for n_tenants, users in scenarios:
        r = run_scenario(n_tenants, users, args.rate, args.seed)
        records.append(r)
        print(f"{n_tenants:>3} {users:>4} {r['energy_arbitrated']:>11.4f} "
              f"{r['energy_naive']:>11.4f} {r['energy_oracle']:>9.4f} "
              f"{100 * r['saving_vs_naive']:>6.1f}% "
              f"{r['violations_arbitrated']:>4}/{r['violations_naive']:<4} "
              f"{r['preemptions']:>7}")
        for tax in r["preemption_tax"]:
            if tax["inflicted"] or tax["suffered"]:
                print(f"      tax {tax['name']}: inflicted "
                      f"{tax['inflicted']:+.4f} J, suffered "
                      f"{tax['suffered']:+.4f} J")
    wins = sum(r["beats_naive"] for r in records)
    need = 1 if args.dry_run else 2
    print(f"arbitrated beats naive FIFO (energy down, violations <=) in "
          f"{wins}/{len(records)} scenarios (gate: >= {need})")
    if args.json:
        doc = dict(benchmark="tenancy_bench",
                   mode="dry-run" if args.dry_run else "full",
                   python=platform.python_version(),
                   platform=platform.platform(),
                   jax_platforms=os.environ.get("JAX_PLATFORMS", ""),
                   gate_wins=wins, gate_needed=need, results=records)
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"wrote {args.json} ({len(records)} scenarios)")

    # ---- occupancy-mode comparison (GPU timeline subsystem) -------------
    t_wins = t_need = 0
    if args.timeline_json:
        t_records = []
        print(f"\n{'T':>3} {'M/t':>4} {'serialized':>11} {'interleaved':>11} "
              f"{'saving':>7} {'viol s/i':>9} {'gapfill':>7} {'dvfs':>5}")
        for n_tenants, users in scenarios:
            r = run_timeline_scenario(n_tenants, users, args.timeline_rate,
                                      args.seed)
            t_records.append(r)
            print(f"{n_tenants:>3} {users:>4} {r['energy_serialized']:>11.4f} "
                  f"{r['energy_interleaved']:>11.4f} "
                  f"{100 * r['saving_vs_serialized']:>6.2f}% "
                  f"{r['violations_serialized']:>4}/"
                  f"{r['violations_interleaved']:<4} "
                  f"{r['gap_fills']:>7} {r['dvfs_rescales']:>5}")
        t_wins = sum(r["beats_serialized"] for r in t_records)
        # dry-run exercises the wiring only: the tiny scenario rarely has
        # enough contention for interleaving to bite
        t_need = 0 if args.dry_run else 2
        print(f"interleaved+DVFS beats serialized (energy down, violations "
              f"<=) in {t_wins}/{len(t_records)} scenarios "
              f"(gate: >= {t_need})")
        doc = dict(benchmark="timeline_bench",
                   mode="dry-run" if args.dry_run else "full",
                   python=platform.python_version(),
                   platform=platform.platform(),
                   jax_platforms=os.environ.get("JAX_PLATFORMS", ""),
                   gate_wins=t_wins, gate_needed=t_need,
                   results=t_records)
        with open(args.timeline_json, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"wrote {args.timeline_json} ({len(t_records)} scenarios)")

    # ---- channel scenario axis (wireless uplink subsystem) --------------
    c_wins = c_need = 0
    if args.channel_json:
        c_records = []
        c_users = 3 if args.dry_run else args.users
        specs = CHANNEL_SCENARIOS[:1] if args.dry_run else CHANNEL_SCENARIOS
        print(f"\n{'scenario':<20} {'aware':>10} {'nominal':>10} "
              f"{'saving':>7} {'viol a/n/s':>11} {'err a/n/s (ms)':>18} "
              f"{'replans':>7}")
        for spec in specs:
            r = run_channel_scenario(spec, c_users, args.channel_rate,
                                     args.seed)
            c_records.append(r)
            print(f"{r['scenario']:<20} {r['energy_aware']:>10.4f} "
                  f"{r['energy_nominal']:>10.4f} "
                  f"{100 * r['saving_vs_nominal']:>6.2f}% "
                  f"{r['violations_aware']:>4}/{r['violations_nominal']}/"
                  f"{r['violations_stagger']:<4} "
                  f"{r['upload_error_aware'] * 1e3:>6.1f}/"
                  f"{r['upload_error_nominal'] * 1e3:.1f}/"
                  f"{r['upload_error_stagger'] * 1e3:<6.1f} "
                  f"{r['channel_replans_nominal']:>7}")
        c_wins = sum(r["beats_nominal"] for r in c_records)
        s_tight = sum(r["stagger_tightens"] for r in c_records)
        print(f"stagger-aware snapshot tightens the aware plan (upload "
              f"error down, violations <=) in {s_tight}/{len(c_records)} "
              f"scenarios")
        # dry-run exercises the wiring only
        c_need = 0 if args.dry_run else 2
        print(f"channel-aware beats nominal-rate planning (energy down, "
              f"violations <=) in {c_wins}/{len(c_records)} scenarios "
              f"(gate: >= {c_need})")
        doc = dict(benchmark="channel_bench",
                   mode="dry-run" if args.dry_run else "full",
                   python=platform.python_version(),
                   platform=platform.platform(),
                   jax_platforms=os.environ.get("JAX_PLATFORMS", ""),
                   gate_wins=c_wins, gate_needed=c_need,
                   results=c_records)
        with open(args.channel_json, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"wrote {args.channel_json} ({len(c_records)} scenarios)")

    failed = wins < need or t_wins < t_need or c_wins < c_need
    if failed:
        print("tenancy/timeline/channel acceptance gate FAILED",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
